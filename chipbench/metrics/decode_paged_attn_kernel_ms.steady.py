"""decode_paged_attn_kernel_ms.steady: mean device ms per decode step run
of the operations whose op path holds the paged attention kernel's name
(``repro.scopes.PAGED_ATTENTION``).  It reads None for a program that has
no such kernel, or whose decode steps did not run it: it tells whether
the decode step attends over the pages in place, and what that costs."""
import bisect

from chipbench import scopes as S


def read(ctx):
    s = S.summary(ctx)
    kernel = getattr(S.names, "PAGED_ATTENTION", None)
    if s is None or kernel is None:
        return None
    runs = sorted(s.runs(S.DECODE_PROGRAM), key=lambda r: r.start)
    starts = [r.start for r in runs]
    tot = 0.0
    for o in s.ops:
        if kernel not in o.path.split("/"):
            continue
        i = bisect.bisect_right(starts, o.start) - 1
        if i >= 0 and o.plane == runs[i].plane and o.end <= runs[i].end:
            tot += o.dur
    return 1e3 * tot / len(runs) if tot > 0 else None
