"""Thin facade over the sketch lowering engine.

One import surface for "how will this sketch launch, and what will it
cost":

    from repro import engine

    plan = make_plan(65_536, 1024)
    lw = engine.lower(plan, engine.LaunchSpec(n=512, dtype="bfloat16"))
    print(lw.describe())                  # the frozen launch record
    print(engine.explain(plan, n=512))    # the full decision trace
    engine.cost_of(lw).modeled_us         # modeled from the SAME record

The engine proper lives in ``repro.kernels.lowering`` (resolution +
execution) and ``repro.roofline.sketch_model.cost_of`` (the modeled cost
of a record); this module only re-exports, so high-level callers do not
need to know the split.
"""
from repro.kernels.lowering import (  # noqa: F401
    GATHER_OPS,
    IMPLS,
    OPS,
    SHARDS,
    LaunchSpec,
    Lowering,
    clear_lowering_cache,
    execute,
    executor_cache_size,
    explain,
    lower,
    lowering_cache_size,
    partial_fits_vmem,
    partial_vmem_bytes,
    v1_working_set_bytes,
)
from repro.roofline.sketch_model import cost_of  # noqa: F401
