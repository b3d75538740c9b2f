"""From a profiler trace (`.xplane.pb`) to the numbers the metrics read.

Which planes are devices and how kernels are named was read off a trace
recorded on the v5e in this PR (`tests/data/`, see PERF.md section 3): a
device is a plane named `/device:TPU:<n>`; its line `XLA Ops` holds one event
for every operation the core ran (start and duration in nanoseconds), `XLA
Modules` one for every program run, `Steps` one for every step.  Host threads
are lines of the plane `/host:CPU`; a `jax.profiler.TraceAnnotation` is an
event of its thread's line, on the same clock as the device's.

`reduce` gives, over the traced steps:

- `window_s`, `busy_s`: first operation's start to last operation's end, and
  the union of the operations' intervals, averaged over the devices;
  `fullest` holds the same for the device that was busy longest;
- `ops`: seconds by operation name, summed over the devices (an operation
  that holds others, a `while` or a `conditional`, is counted without them);
  every Pallas kernel and collective, and of the rest the 200 longest;
  `groups`: the same by kind of operation, all of them, for the breakdown;
- `exposed_collective_s`: time in collective operations during which nothing
  else ran on that device, averaged over the devices;
- `gaps`: the fullest device's idle gaps by what the host was doing (the
  span, of those given, that covers most of the gap).
"""
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
PALLAS = 'custom_call_target="tpu_custom_call"'
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


def load(path):
    """`{plane: {line: [(name, start_ns, end_ns), ...]}}` of one file."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                start = float(ev.start_ns)
                evs.append((ev.name, start, start + float(ev.duration_ns)))
    return out


def union(intervals):
    """Sorted, merged `[start, end]` lists."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def length(merged):
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """Length of merged `a` not covered by merged `b`."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def self_times(events):
    """`{name: ns}` with an event's time less that of the events wholly
    inside it.  (Consecutive operations of a core overlap by a little; that
    is not holding, and nothing is taken off for it.)"""
    out = {}
    stack = []           # [name, end, held, duration]

    def close():
        n, _, held, dur = stack.pop()
        out[n] = out.get(n, 0.0) + dur - held

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and (stack[-1][1] <= s or e > stack[-1][1]):
            close()
        if stack:
            stack[-1][2] += e - s
        stack.append([name, e, 0.0, e - s])
    while stack:
        close()
    return out


def reduce(planes, spans=()):
    devices = {int(m.group(1)): lines for name, lines in planes.items()
               if (m := DEVICE_PLANE.match(name)) and lines.get(OPS_LINE)}
    if not devices:
        return None
    per, ops, busy_of = {}, {}, {}
    for d, lines in devices.items():
        evs = lines[OPS_LINE]
        busy = busy_of[d] = union((s, e) for _, s, e in evs)
        coll = union((s, e) for n, s, e in evs if COLLECTIVE.search(n))
        rest = union((s, e) for n, s, e in evs if not COLLECTIVE.search(n))
        per[d] = {"window_s": (busy[-1][1] - busy[0][0]) / 1e9,
                  "busy_s": length(busy) / 1e9,
                  "exposed_collective_s": subtract(coll, rest) / 1e9,
                  "steps": len(lines.get("Steps", []))}
        for n, ns in self_times(evs).items():
            ops[n] = ops.get(n, 0.0) + ns / 1e9
    n = len(per)
    fullest = max(per, key=lambda d: per[d]["busy_s"])
    # the fullest device's gaps, by what the host was doing
    host = [(n_, s, e) for name, lines in planes.items()
            if name.startswith("/host:") for evs in lines.values()
            for n_, s, e in evs if n_ in spans]
    gaps = {}
    busy = busy_of[fullest]
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        best, cover = "other", 0.0
        for n_, s, e in host:
            c = min(e, s1) - max(s, e0)
            if c > cover:
                best, cover = n_, c
        gaps[best] = gaps.get(best, 0.0) + (s1 - e0) / 1e9
    # every Pallas kernel and collective by its whole name, for the readers
    # that look for one; of the rest the 200 that took longest
    top = sorted(ops.items(), key=lambda kv: -kv[1])
    keep = [kv for kv in top if PALLAS in kv[0] or COLLECTIVE.search(kv[0])]
    keep += [kv for kv in top if kv not in keep][:200]
    return {
        "devices": n,
        "window_s": sum(p["window_s"] for p in per.values()) / n,
        "busy_s": sum(p["busy_s"] for p in per.values()) / n,
        "exposed_collective_s":
            sum(p["exposed_collective_s"] for p in per.values()) / n,
        "steps": per[fullest]["steps"],
        "fullest": dict(per[fullest], device=fullest),
        "ops": dict(keep),
        "groups": groups(ops),
        "gaps": gaps,
    }


def groups(ops):
    """Seconds by kind of operation: an event's name is the whole HLO
    instruction (`%fusion.12 = bf16[...] fusion(...)`), so the kind is its
    name without the number; a Pallas kernel (`tpu_custom_call`) is named
    after the scope it was called in (`%attn.8`) and is marked as one."""
    out = {}
    for name, s in ops.items():
        kind = re.sub(r"\.\d+$", "", name.split(" = ")[0].lstrip("%"))
        if PALLAS in name:
            kind += " (pallas)"
        out[kind] = out.get(kind, 0.0) + s
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def seconds_in(ops, pattern, exclude=None):
    """Summed seconds of the operations of `ops` (`reduce(...)["ops"]`)
    whose name, a whole HLO instruction, matches `pattern` and not
    `exclude`: how the kernel readers under `metrics/` find their events."""
    return sum(s for name, s in ops.items() if re.search(pattern, name)
               and not (exclude and re.search(exclude, name)))


def find(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def reduce_dir(trace_dir, spans=()):
    path = find(trace_dir)
    return reduce(load(path), spans) if path else None


def listing(planes, top=12):
    """What a trace holds, for a look by hand."""
    out = []
    for pname, lines in planes.items():
        for lname, evs in lines.items():
            names = {}
            for n, s, e in evs:
                names[n] = names.get(n, 0.0) + (e - s)
            best = sorted(names.items(), key=lambda kv: -kv[1])[:top]
            out.append(f"{pname} | {lname} | {len(evs)} events | " + ", ".join(
                f"{n}={ns / 1e6:.3f}ms" for n, ns in best))
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(listing(load(sys.argv[1])))
