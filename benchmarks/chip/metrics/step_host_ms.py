"""Median host time of the engine's own work in a step: over the traced
window's ``serve.step`` spans, each span's length less the ``.sync`` spans
nested in it, where the host waits on the device."""
import bisect

import numpy as np


def read(rec):
    tr = rec["trace"]
    if not tr:
        return None
    spans = sorted(tr["spans"], key=lambda s: s[1])
    syncs = [s for s in spans if s[0].endswith(".sync")]
    starts = [a for _, a, _ in syncs]
    own = []
    for name, a, b in spans:
        if name == "serve.step":
            i = bisect.bisect_left(starts, a)
            j = bisect.bisect_right(starts, b)
            own.append(b - a - sum(e - s for _, s, e in syncs[i:j] if e <= b))
    return 1e3 * float(np.median(own)) if own else None
