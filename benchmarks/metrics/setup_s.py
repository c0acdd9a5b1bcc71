"""`setup_s` (s, end to end, host clock): process start to the window's
opening: imports, reaching the chip, target load, worker build, the
warm units, every compilation."""


def read(obs):
    return obs["t_open"] - obs["t_start"]
