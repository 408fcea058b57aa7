"""Share of the attention sites that AutoChunk's kernel dispatch
(``core/kernel_dispatch.py``) sent to the bool-mask kernel rather than
the position-computed one, over the run's process: 100 x (attention
dispatches - ``kernel_dispatch_computed_mask``) /
``kernel_dispatch_attention``.  The ``kernel_dispatch_bool_mask.<reason>``
counters say why."""
from repro.core import stats


def read(data):
    counts = stats.snapshot()
    n = counts.get("kernel_dispatch_attention", 0)
    if not n:
        return None
    return 100 * (n - counts.get("kernel_dispatch_computed_mask", 0)) / n
