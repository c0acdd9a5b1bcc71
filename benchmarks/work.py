"""Integer operations one candidate costs: with the candidates hashed,
the numerator of `mask_kernel_roofline` (a traced slice's,
`slice_lanes`) and of `step_mfu` (the whole window's).

The count is of what no correct implementation can avoid, so that the
share reads the same work whatever implements the kernel and cannot
honestly pass 100 %.  One operation is one 32-bit add, and, or, xor,
not-combined-with-another-op, shift or ROTATE on one lane.  Taken as
given, in the kernel's favour:

- a rotate is ONE operation (the v5e vector unit has none and pays a
  shift, a shift and an or: that cost is the kernel's, and shows as a
  lower share);
- message words a short mask leaves constant are folded into the round
  constant (`M[k] + K[i]` is one constant), and an all-zero word under
  a zero constant costs nothing;
- the boolean functions at their cheapest known forms (MD5 F, G, I:
  3 operations, H: 2; MD4 F: 3, G as `(b & (c | d)) | (c & d)`: 4,
  H: 2);
- a single target is met in the middle: the trailing steps whose
  message word is constant are undone once on the target and never run
  per candidate (hashcat does this); a list of targets cannot be, and
  pays every step;
- the add of the initial state is folded into the target;
- index -> candidate decode is not counted (an implementation can step
  the odometer instead of dividing), nor the compare, the probe bitmap
  or the hit reduction.

Source: RFC 1321 (MD5) and RFC 1320 (MD4) for the step functions and
word orders; the folding rules above are this file's.  `peak_int32` is
the other half of the yardstick: the table of measured peaks,
`peaks.json`, keyed by `device_kind`.
"""

import json
import os

#: message word each step reads (RFC 1321 section 3.4)
_MD5_K = ([i for i in range(16)]
          + [(5 * i + 1) % 16 for i in range(16)]
          + [(3 * i + 5) % 16 for i in range(16)]
          + [(7 * i) % 16 for i in range(16)])
_MD5_F = [3] * 16 + [3] * 16 + [2] * 16 + [3] * 16

#: RFC 1320: rounds 1, 2 and 3
_MD4_K = ([i for i in range(16)]
          + [(i % 4) * 4 + i // 4 for i in range(16)]
          + [0, 8, 4, 12, 2, 10, 6, 14, 1, 9, 5, 13, 3, 11, 7, 15])
_MD4_F = [3] * 16 + [4] * 16 + [2] * 16


def _varying_words(n_bytes):
    """Message words that differ between candidates: the password's
    bytes and the 0x80 that follows them."""
    return set(range((n_bytes + 1 + 3) // 4))


def _trailing_constant(order, varying):
    n = 0
    for k in reversed(order):
        if k in varying:
            break
        n += 1
    return n


def md5_ops(length, n_targets=1):
    """MD5 of a `length`-byte password (one block)."""
    varying = _varying_words(length)
    steps = []
    for i in range(64):
        # a = b + rol(a + f(b,c,d) + [M[k] +] const, s):
        # f, add, add const, rotate, add b; one more add where M[k]
        # varies
        steps.append(_MD5_F[i] + 4 + (_MD5_K[i] in varying))
    undone = _trailing_constant(_MD5_K, varying) if n_targets == 1 else 0
    return sum(steps[:64 - undone])


def md4_ops(n_bytes, n_targets=1):
    """MD4 of an `n_bytes`-byte message (one block)."""
    varying = _varying_words(n_bytes)
    steps = []
    for i in range(48):
        k = _MD4_K[i]
        # a = rol(a + f(b,c,d) + M[k] + const, s): f, add, rotate; one
        # more add for a round constant or a constant non-zero word
        # (folded together), and one more where M[k] varies
        const = i >= 16 or (k == 14 and k not in varying)
        steps.append(_MD4_F[i] + 2 + bool(const) + (k in varying))
    undone = _trailing_constant(_MD4_K, varying) if n_targets == 1 else 0
    return sum(steps[:48 - undone])


def ops_per_candidate(engine, length, n_targets=1):
    """engine: the configuration's `engine`; length: the mask's length
    in characters."""
    if engine == "md5":
        return md5_ops(length, n_targets)
    if engine == "ntlm":            # MD4 over UTF-16LE: two bytes a char
        return md4_ops(2 * length, n_targets)
    raise KeyError(f"no operation count for engine {engine!r}")


def ops_of(obs):
    """Operations a candidate of the run's job costs."""
    cfg = obs["cfg"]
    mask_len = len(obs["plan"].plants[0].plain)
    return ops_per_candidate(cfg["engine"], mask_len, cfg["targets"])


#: `slice_lanes` refuses a slice whose kernel calls swept more than
#: this many times the candidates in flight: no program does (a window
#: whose hit buffer overflowed is swept a second time and never a
#: third, so a job reads at most 2), and an entry driver's
#: `KERNEL_EVENT` that matches other events beside the hash kernel does
#: (three a batch read 3.2 once, PR 29)
MAX_SWEEPS = 2.5


def slice_lanes(obs):
    """(swept, in_flight) of a traced run's slice, both over all chips:
    the lanes its kernel calls swept (the calls that lie wholly inside
    the slice, a chip, x `--batch` lanes a call and chip: that is what
    the flag means, and the one thing read from the configuration, x
    chips), and the candidates of the units in flight during the slice
    by the harness's own ledger: every unit leased before the slice
    ended (it ends with the window: a one-target job's tail is leased
    after it) and not completed before it began, each counted whole.
    `mask_kernel_roofline`'s numerator is the first, as the kernel was
    called; `kernel_sweeps` is the one over the other.  Over
    `MAX_SWEEPS` the calls were miscounted and every `kernels` metric
    with them: an error, not a number."""
    tr = obs["trace"]
    swept = (tr["kernel_calls"] * obs["cfg"]["flags"]["batch"]
             * obs["n_devices"])
    t1 = obs["t_close"]
    t0 = t1 - tr["window_s"]
    in_flight = sum(n for _, n, leased, done
                    in obs["units"] + obs["tail_units"]
                    if leased < t1 and (done is None or done > t0))
    if swept > MAX_SWEEPS * in_flight:
        raise RuntimeError(
            f"{tr['kernel_calls']} kernel calls a chip swept {swept} "
            f"lanes where {in_flight} candidates were in flight: more "
            f"than {MAX_SWEEPS} sweeps of each, which no program makes; "
            f"the entry driver's KERNEL_EVENT matches more than the "
            f"hash kernel")
    return swept, in_flight


def peak_int32(device_kind):
    """The measured int32 op/s of one chip of that kind; a kind the
    table does not hold is an error, not a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as fh:
        return json.load(fh)["peaks"][device_kind]["int32_ops_per_s"]
