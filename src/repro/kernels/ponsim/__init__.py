"""On-device PON cycle engine: kernel (Pallas) / ops (program) / ref.

The jit backend of ``repro.net.engine`` (``backend="jit"``): one
``lax.while_loop`` device program per transfer phase, with the traffic
sampler fused in and the waterfill grant step as a Pallas TPU kernel
(XLA oracle elsewhere).  Mirrors the ``repro.kernels.traffic``
kernel/ops/ref layout.
"""
from repro.kernels.ponsim.ops import (  # noqa: F401
    HISTORY_CYCLES,
    compile_count,
    host_fallbacks,
    run_phase_device,
)
from repro.kernels.ponsim.ref import (  # noqa: F401
    cps_waterfill_ref,
    sample_window_ref,
    waterfill_grants_ref,
    waterfill_grants_xla,
)
