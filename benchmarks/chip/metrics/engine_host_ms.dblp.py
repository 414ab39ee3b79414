"""Host milliseconds per serve-engine step in which the device waits on
the engine: over the ``engine.step`` spans that contain a
``service.tick.wait`` (the fetch of a tick's residuals, which waits for
the device), the median of the step's duration less its waits.  The
first such step also builds the layout and loads the tick program; the
median leaves it out."""
import statistics

import program_spans


def read(ctx):
    recs = program_spans.records()
    by_index = {r.index: r for r in recs}
    waits = {}  # engine.step index -> ns spent in its tick waits
    for r in recs:
        if r.name != "service.tick.wait":
            continue
        up = by_index.get(r.parent)
        while up is not None and up.name != "engine.step":
            up = by_index.get(up.parent)
        if up is not None:
            waits[up.index] = waits.get(up.index, 0) + r.end_ns - r.start_ns
    if not waits:
        return None
    return statistics.median(
        1e-6 * (by_index[i].end_ns - by_index[i].start_ns - w)
        for i, w in waits.items())
