"""One of the program's own counters over the sum of all counters of a
prefix, in per cent, over every report of one kind of process
(`benchmark/spans.py`): `feed.bytes.ring` over `feed.bytes.*` is the share
of the fed bytes that rode the shared-memory ring and not the manager's
socket.  Counters have no clock: the ratio is over the whole run.  Nothing
where the program has no such report, or counted nothing."""
import spans as spans_mod


def read(run, source, counter, over):
    loaded = spans_mod.load(run)
    if loaded is None:
        return None
    return compute(loaded, source, counter, over)


def compute(loaded, source, counter, over):
    part = total = 0
    for rep in loaded[source]:
        for name, n in (rep.get("counters") or {}).items():
            if name.startswith(over):
                total += n
                if name == counter:
                    part += n
    return 100.0 * part / total if total else None
