"""encode_p50_ms: median, over every encode request due in the window, of
the time from its due time to its result. A request still waiting when
the window closes enters at its wait so far; a failed one is counted in
``failed``, not here."""
import numpy as np

from chipbench.pump import waits


def read(ctx):
    w = waits(ctx.reqs, ctx.window)
    if not w:
        return None
    return float(np.percentile(np.asarray(w), 50) * 1e3)
