"""Latent attention's core against its roofline over the decode phase of
the traced batch: the sum over its executions of the least time that
``attention/core``'s needed work takes at the chip's peaks (the family's
``core_counts`` at each execution's filled positions,
``bench/peaks.json``), over the sum of that scope's device times (profiler
trace and the step's HLO).  Silent where the family counts no core work,
or the trace pairs no decode execution with its scopes."""
from bench import reference


def read(r):
    fam = reference.family(r.model)
    steps = r.decode_steps()
    if not hasattr(fam, "core_counts") or not steps or not r.decode_scopes \
            or len(steps) != len(r.decode_scopes):
        return None
    B = r.traced_batch().generated.shape[0]
    least = spent = 0.0
    for (filled, _), by_scope in zip(steps, r.decode_scopes):
        flops, nbytes = fam.core_counts(r.model, B, filled)
        least += max(flops / r.peak["bf16_flops_per_s"],
                     nbytes / r.peak["hbm_bytes_per_s"])
        spent += sum(v for k, v in by_scope.items()
                     if k == "attention/core"
                     or k.startswith("attention/core/"))
    return 100.0 * least / spent if spent else None
