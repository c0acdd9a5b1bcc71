"""One module an engine: `engines/<engine>.py`, named as the
configuration's `engine` with `-` written `_`, found by that name the
way `entries/<entry>.py` and `metrics/<name>.py` are.  A new engine is
a new file here; nothing else of the benchmark names one.

An engine module imports nothing of `dprf_tpu` and holds the four
things the harness asks of an engine:

`target_line(plain, rng, cfg) -> str`  the hash-file line of a planted
    password.  `rng` (a `random.Random`) draws what a salted format
    needs beside the password; an unsalted engine ignores it.
`filler_line(rng, cfg) -> str`  a line no candidate of the run matches.
`matches(line, plain) -> bool`  whether the reference hashes `plain`
    to that line (what `compare.potfile_wrong` asks of every line the
    program wrote to its potfile).
`ops_per_candidate(length, cfg) -> int`  the integer operations one
    candidate of `length` characters costs, by the rules of `work.py`,
    with the engine's own source and folding written beside it.
"""

import importlib
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name, root=None):
    """The module of the engine a configuration names.  `root`: a data
    root whose `engines/` directory is searched behind this one (tests
    keep engines of their own there, as they keep configurations); once
    searched it stays so, and later calls need not name it."""
    extra = os.path.join(root, "engines") if root else None
    if extra and extra not in __path__:
        __path__.append(extra)
    module = name.replace("-", "_")
    try:
        return importlib.import_module(f"{__name__}.{module}")
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{module}":
            raise
        raise LookupError(
            f"engine {name!r} has no module: "
            f"{os.path.join(HERE, module + '.py')} is missing") from None
