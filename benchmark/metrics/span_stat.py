"""A statistic of the program's own spans (`benchmark/spans.py`) over the
measured window, from the reports of one kind of process (`source`:
`feeder`, `node` or `driver`):

- `share`: per cent of the window covered by the union of the spans named,
  less the milliseconds they say they only waited (`less`: span name ->
  attribute, as `feed.ring_write`'s `blocked_ms`);
- `absent`: per cent of the window NOT covered by them;
- `median_ms`: the median duration of those that end inside the window;
- `sum_s`, `union_s`: seconds in those that end before the window starts
  (`when` `before`) or inside it, summed, or as the union of their
  intervals (spans that lie inside one another, as JAX's trace events of
  nested `jit`s do, are then counted once).

Nothing where the program has no such report (the PR's parent), where the
report lost spans of the window, or where no such span was recorded; but 0
where none was recorded and a span named in `given` was: the recorder was
there and had nothing to record (no backend compile in a run whose every
program came from the persistent cache)."""
import spans as spans_mod


def read(run, source, spans, stat, less=None, when="window", given=()):
    loaded = spans_mod.load(run)
    if loaded is None:
        return None
    return compute(loaded, source, spans, stat, less, when, given)


def compute(loaded, source, spans, stat, less=None, when="window", given=()):
    reports, window = loaded[source], loaded["window"]
    if when == "before":
        window = (float("-inf"), window[0])
    if not spans_mod.whole(reports, window):
        return None
    if source == "feeder" and spans_mod.tasks_overlap(reports):
        return None
    found = spans_mod.named(reports, spans)
    seconds = window[1] - window[0]
    if stat in ("share", "absent"):
        if not found:
            return None
        covered = spans_mod.union_s(found, window)
        for s in found:
            attr = (less or {}).get(s["name"])
            if attr and window[0] <= s["t1"] <= window[1]:
                covered -= s["attrs"].get(attr, 0.0) / 1e3
        share = 100.0 * max(covered, 0.0) / seconds
        return share if stat == "share" else 100.0 - share
    inside = [s for s in found if window[0] <= s["t1"] <= window[1]]
    if not inside:
        return 0.0 if stat != "median_ms" and spans_mod.named(
            reports, given) else None
    if stat == "median_ms":
        return spans_mod.median(s["dur_ms"] for s in inside)
    if stat == "sum_s":
        return sum(s["dur_ms"] for s in inside) / 1e3
    if stat == "union_s":
        return spans_mod.union_s(inside, (min(s["t0"] for s in inside),
                                          window[1]))
    raise ValueError(f"no statistic {stat!r}")
