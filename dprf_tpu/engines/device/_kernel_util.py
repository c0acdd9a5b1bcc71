"""Shared per-kind Pallas-step builder for the per-target-sweep
workers (pdf, 7z, krb5aes): build the kernel step AND force its
compile at worker construction, so a trace-time error or a Mosaic
compile failure raises there with the compiler's message -- not
mid-job, and never as a silent switch to the much slower XLA step.
Risky shapes stay gated off by their eligibility predicates until
measured."""

from __future__ import annotations


def kind_kernel_step(build, warmup):
    """build() -> lazily-jitted step; warmup(step) must invoke it once
    (hard_sync'd) to force the device compile.  Returns the warmed
    step."""
    step = build()
    warmup(step)
    return step
