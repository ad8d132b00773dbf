"""90th percentile, over the requests due in the window, of due time ->
decision, in the window or in the drain after it.  In the steady cell it
falls among the requests that the failure holds up; the 75th falls on the
edge of that cluster and swings with it."""
from bench.harness import stats


def read(ctx):
    lat = ctx["latencies_ms"]
    return stats.percentile(lat, 90) if len(lat) else None
