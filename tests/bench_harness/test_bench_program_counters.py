"""The readers of the program's inner-boundary counters: the right value
on a synthetic window, None where the program keeps no such counter."""
import pytest

from bench.harness import catalog

COUNTERS = {
    "residual.rebuild_ms{site=classify}": 300.0,
    "residual.rebuild_ms{site=commit}": 500.0,
    "residual.rebuilds{site=commit}": 4.0,
    "timing.solve_ms": 400.0,
    "timing.dp_wait_ms": 300.0,
    "timing.reconstruct_ms": 20.0,
    "placer.solves": 10.0,
    "placer.solves_by_impl{kernel_impl=ref}": 99.0,
    "plane.queue_wait_ms": (8, 2000.0),
    "placer.remap_ms": 900.0,
    "placer.remapped": 2.0,
    "placer.dropped": 1.0,
}

EXPECTED = {
    "residual_rebuild_ms.steady": 800.0 / 4,
    "residual_rebuild_ms.overload": 800.0 / 4,
    "dp_wait_ms.steady": 30.0,
    "dp_wait_ms.overload": 30.0,
    "reconstruct_ms.steady": 2.0,
    "reconstruct_ms.overload": 2.0,
    "queue_wait_ms.steady": 250.0,
    "remap_ms.steady": 300.0,
}


def _ctx(counters, decisions=4):
    return {"counters": counters, "decisions": decisions, "window_s": 51.0,
            "spans_s": {}, "trace": None}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_its_counters(name):
    read = catalog.metric_reader(name)
    assert read(_ctx(COUNTERS)) == pytest.approx(EXPECTED[name])
    # a program without the counter (the parent of this instrumentation)
    old = {k: v for k, v in COUNTERS.items()
           if k.startswith(("timing.solve_ms", "placer.solves"))}
    assert read(_ctx(old)) is None
    assert read(_ctx({}, decisions=0)) is None


def test_every_reader_is_in_the_benchmark():
    b = catalog.benchmark()
    entries = {m["name"]: m for m in b["per_layer"]}
    for name in EXPECTED:
        m = entries[name]
        assert (m["source"], m["unit"], m["better"]) == (
            "program_counter", "ms", "lower")
        cell = "flat4k-" + name.rsplit(".", 1)[1]
        assert m["workloads"] == [cell]
