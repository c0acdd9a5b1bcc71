"""`target_load_s` (s; layer: entry; program span): the seconds of the
job's `targets` station, from its own `ran` line (`host=targets:..`):
hash-file parse, the probe table's build and its upload.  Nothing on a
program without the station.  Moves `setup_s`."""


def read(obs):
    host = (obs["log"].get("ran") or {}).get("host", "")
    for field in host.split(","):
        name, _, seconds = field.partition(":")
        if name == "targets":
            return float(seconds)
    return None
