"""One server on one chip, wired as ``repro.launch.serve`` wires it.

On one chip ``PipeBoostEngine.enable_pipeline_prefill`` declines, so every
admission takes the batcher's fused single prefill; the engine is left
out.  The cell file gives ``n_slots`` and ``max_len``."""
from repro.core.adapter_scheduler import EpochSchedulerPolicy
from repro.serving.engine import ServingEngine


def build(arch, params, spec):
    n = int(spec["n_slots"])
    return ServingEngine(arch, params, n_slots=n, max_len=int(spec["max_len"]),
                         policy=EpochSchedulerPolicy(max_batch=n))
