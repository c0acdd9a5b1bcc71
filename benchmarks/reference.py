"""The plain reference: what a mask job means, written once, slowly,
with nothing of `dprf_tpu` in it.

- index -> candidate: hashcat's built-in charsets (`?l ?u ?d ?s ?a`,
  `??`, literals) and a mixed-radix decode with the RIGHTMOST mask
  position as the least-significant digit (the order the
  configuration files state under `index_order`);
- the potfile's reading: `hash line:plain`, as hashcat writes it.

What an engine hashes a candidate to is the engine's own file,
`engines/<engine>.py`; `md5`, `md4`, `ntlm` and `digest_hex` are still
importable from here under their old names.

The comparison (`compare.py`) uses it to say which plants a run's
covered intervals contain and to read every line the program wrote to
its potfile; the generator (`traffic.py`) to make the plants from the
seed.
"""

import engines
from engines.md5 import md5                 # noqa: F401
from engines.ntlm import md4, ntlm          # noqa: F401

LOWER = bytes(range(ord("a"), ord("z") + 1))
UPPER = bytes(range(ord("A"), ord("Z") + 1))
DIGIT = bytes(range(ord("0"), ord("9") + 1))
#: hashcat's ?s: the 33 printable ASCII symbols, space included
SYMBOL = (bytes(range(0x20, 0x30)) + bytes(range(0x3A, 0x41))
          + bytes(range(0x5B, 0x61)) + bytes(range(0x7B, 0x7F)))
CHARSETS = {"l": LOWER, "u": UPPER, "d": DIGIT, "s": SYMBOL,
            "a": LOWER + UPPER + DIGIT + SYMBOL}


def mask_charsets(mask):
    """Mask string -> one charset per position, left to right."""
    out, i = [], 0
    while i < len(mask):
        if mask[i] == "?":
            sel = mask[i + 1]
            out.append(b"?" if sel == "?" else CHARSETS[sel])
            i += 2
        else:
            out.append(mask[i].encode("latin-1"))
            i += 1
    return out


def keyspace(mask):
    n = 1
    for cs in mask_charsets(mask):
        n *= len(cs)
    return n


def candidate(mask, index):
    """The mask's candidate at a keyspace index (odometer order)."""
    out = bytearray()
    for cs in reversed(mask_charsets(mask)):
        index, digit = divmod(index, len(cs))
        out.append(cs[digit])
    if index:
        raise ValueError("index beyond the mask's keyspace")
    return bytes(reversed(out))


def digest_hex(engine, password):
    """An unsalted engine's hash line of a password."""
    return engines.load(engine).target_line(password, None, None)


# ---------------------------------------------------------------------------
# potfile lines, as hashcat writes them: `hash:plain`, a plain with
# bytes outside printable ASCII (or a colon) as `$HEX[..]`

def decode_plain(text):
    if text.startswith("$HEX[") and text.endswith("]"):
        return bytes.fromhex(text[5:-1])
    return text.encode("latin-1")


def read_potfile(path):
    """[(hash line, plain bytes)] of a potfile, in file order."""
    out = []
    try:
        with open(path, encoding="latin-1") as fh:
            for line in fh.read().splitlines():
                if line:
                    h, _, p = line.partition(":")
                    out.append((h, decode_plain(p)))
    except FileNotFoundError:
        pass
    return out
