"""`ttfh_s` (s, end to end, host clock): the window's opening to the
first planted password being in the potfile (the line written and
synced).  Nothing where no plant was found in the window."""


def read(obs):
    plants = {p.line for p in obs["plan"].plants_in("window")}
    stamps = [t for t, line in obs["potfile_stamps"] if line in plants]
    if not stamps:
        return None
    return min(stamps) - obs["t_open"]
