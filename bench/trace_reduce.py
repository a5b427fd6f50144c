"""Reduction of a profiler trace to what the per-layer metrics read.

Two steps, kept apart so the second is tested on a small recorded
trace without the profiler:

* `events(path, n_devices)` reads an `.xplane.pb` with
  `jax.profiler.ProfileData`: for each chip the operations of its
  "XLA Ops" line (HLO name, whether it is a loop or call that spans
  other operations, start, duration), and the host spans the harness
  wrote (`bench.*` TraceAnnotations), on the same clock.
* `summarize(ev)` turns those into the window, each chip's busy time
  (the union of its operations' intervals, loops included), the idle
  gaps with the host span the host was in during each, the self time
  of operations by name (loops and calls left out, since their bodies
  are counted) and by kernel, and the collectives with the part of
  each that no other operation on that chip overlaps (loops left out,
  or a collective inside a loop would always look hidden).

On a TPU an operation's name is its HLO instruction; a Pallas kernel's
is the name of the function that called `pallas_call`
(`%flash_attention_hsd.27`), which is how kernels are found.
The window is the first host span's start to the last one's end.
"""
import bisect
import re

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.I)
# loop and call operations span the operations of their bodies
CONTAINER = re.compile(r"[\s}](while|conditional|call)\(")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def op_name(text):
    """An operation's name from its HLO text (`%fusion.12 = ...`)."""
    return text.split(" = ", 1)[0]


def events(path, n_devices):
    """Device operations and harness spans of one trace, as plain lists:
    {"devices": {chip: [[name, is_container, start_ns, dur_ns], ...]},
     "host": [[name, start_ns, dur_ns], ...]}."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < n_devices:
            ops = dev.setdefault(str(int(m.group(1))), [])
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    ops.append([op_name(ev.name),
                                bool(CONTAINER.search(ev.name)),
                                int(ev.start_ns), int(ev.duration_ns)])
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    for ops in dev.values():
        ops.sort(key=lambda o: o[2])
    host.sort(key=lambda h: h[1])
    return {"devices": dev, "host": host}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, t0, t1):
    return [[max(s, t0), min(e, t1)] for s, e in intervals
            if e > t0 and s < t1]


def _overlap(a, merged, starts):
    """Length of interval a covered by the sorted disjoint `merged`."""
    s, e = a
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    cov = 0
    while i < len(merged) and merged[i][0] < e:
        cov += max(0, min(e, merged[i][1]) - max(s, merged[i][0]))
        i += 1
    return cov


def _host_span_at(host, t):
    """The innermost (latest-starting) harness span open at time t."""
    best = "none"
    for name, s, d in host:
        if s > t:
            break
        if s <= t <= s + d:
            best = name
    return best


def summarize(ev, kernels=None):
    """kernels: {kernel: regex on an operation's name}."""
    host = ev["host"]
    if not host:
        raise ValueError("the trace holds no harness span")
    t0 = min(h[1] for h in host)
    t1 = max(h[1] + h[2] for h in host)
    window = t1 - t0
    chips = sorted(ev["devices"], key=int)
    busy, gaps, by_name, spans = {}, [], {}, {}
    kern = {k: {"s": 0.0, "n": 0} for k in (kernels or {})}
    coll = {"s": 0.0, "exposed_s": 0.0, "n": 0}
    for c in chips:
        ops = ev["devices"][c]
        iv = _clip([[o[2], o[2] + o[3]] for o in ops], t0, t1)
        spans[c] = iv
        merged = _union(iv)
        busy[c] = sum(e - s for s, e in merged) / 1e9
        prev = t0
        for s, e in merged + [[t1, t1]]:
            if s > prev:
                gaps.append([_host_span_at(host, (prev + s) // 2),
                             (s - prev) / 1e9, c])
            prev = max(prev, e)
        compute = _union(_clip([[o[2], o[2] + o[3]] for o in ops
                                if not (o[1] or COLLECTIVE.search(o[0]))],
                               t0, t1))
        cstarts = [s for s, _ in compute]
        for name, container, s, d in ops:
            if s + d <= t0 or s >= t1 or container:
                continue
            by_name[name] = by_name.get(name, 0.0) + d / 1e9
            for k, pat in (kernels or {}).items():
                if re.search(pat, name):
                    kern[k]["s"] += d / 1e9
                    kern[k]["n"] += 1
            if COLLECTIVE.search(name):
                coll["s"] += d / 1e9
                coll["n"] += 1
                coll["exposed_s"] += (d - _overlap([s, s + d], compute,
                                                   cstarts)) / 1e9
    n = max(len(chips), 1)
    gaps.sort(key=lambda g: -g[1])
    return {"window_s": window / 1e9, "chips": len(chips),
            "busy_s": sum(busy.values()) / n, "busy_by_chip_s": busy,
            "idle_gaps": gaps,
            "op_s": sorted(by_name.items(), key=lambda kv: -kv[1]),
            "kernels": kern, "collectives": coll,
            "ops_by_chip": spans, "host": host}


def reduce(path, n_devices, kernels=None):
    return summarize(events(path, n_devices), kernels)


def breakdown(red):
    """The ten device operations that took most time (seconds summed over
    the chips), and the ten longest idle gaps named by the harness span
    the host was in."""
    return {"device_ops": [[n, s] for n, s in red["op_s"][:10]],
            "idle_gaps": [[f"{g[0]} (chip {g[2]})", g[1]]
                          for g in red["idle_gaps"][:10]]}
