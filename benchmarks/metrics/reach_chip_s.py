"""`reach_chip_s` (s; layer: entry; host clock): process start to
`jax.devices()` having returned.  Moves `setup_s`."""


def read(obs):
    return obs.get("reach_chip_s")
