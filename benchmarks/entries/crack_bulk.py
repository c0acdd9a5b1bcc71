"""Entry driver `crack_bulk`: `entries/crack.py` as it stands, for a job
whose program holds other custom calls than its hash kernel.

A bulk list's program looks every digest up in a table in HBM, and the
TPU compiler gives an XLA gather custom calls of its own
(`AssumeGatherIndicesInBound`, `ConcatBitcast`, `AllocateBuffer`): the
text `crack.KERNEL_EVENT` matches, ` custom-call(`, would count those
as calls of the hash kernel: three events a batch and more, where
`work.slice_lanes` raises, and `kernel_pct` and `mask_kernel_roofline`
would read the gathers' seconds.
An `XLA Ops` event's text is the whole instruction, so what is matched
here is the one attribute only a Pallas call carries.  (The kernel's
own name, `%mask_digest_kernel.<n>`, will not do: the fusions that read
its output name it among their operands, three events a batch.)  A
program with no Pallas kernel in it (the parent's plain XLA pipeline)
has no such event: `kernel_pct`, `mask_kernel_roofline` and
`probe_stage_pct` then read nothing.
"""

from entries.crack import (WARM_UNITS, audit, judge_lanes,  # noqa: F401
                           run)

KERNEL_EVENT = 'custom_call_target="tpu_custom_call"'
