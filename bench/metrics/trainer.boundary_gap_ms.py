"""Mean device-idle gap at each superstep boundary: on each chip, from the
last operation that ended before the harness dispatched superstep i+1 to
the first operation that started after, located by the harness's
`bench.dispatch` spans in the trace."""
import bisect


def read(ctx):
    red = ctx["reduced"]
    starts = [s for n, s, _ in red["host"] if n == "bench.dispatch"][1:]
    gaps = []
    for ops in red["ops_by_chip"].values():
        begins = [o[0] for o in ops]
        ends = sorted(o[1] for o in ops)
        for t in starts:
            i = bisect.bisect_right(ends, t) - 1
            j = bisect.bisect_left(begins, t)
            if i >= 0 and j < len(begins):
                gaps.append(begins[j] - ends[i])
    return sum(gaps) / len(gaps) / 1e6 if gaps else None
