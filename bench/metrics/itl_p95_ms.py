"""95th percentile of the gaps between consecutive tokens of one
request, over every gap whose later token came in the window.  Tokens
that arrive together (a prefill's first token and the decode round of
the same step) count as a gap of 0."""

import numpy as np


def read(rec):
    gaps = [b - a for s in rec.served
            for a, b in zip(s.stamps, s.stamps[1:]) if rec.in_window(b)]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
