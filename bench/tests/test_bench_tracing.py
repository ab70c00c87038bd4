"""The reduction from a device trace to busy time, idle share and the
breakdown, on small synthetic traces and on one recorded on the CPU."""
import pytest

import tracing


def test_union_and_gaps():
    busy = tracing.union([(2.0, 3.0), (0.0, 1.0), (0.5, 1.5), (3.0, 3.5),
                          (5.0, 5.0)])
    assert busy == [(0.0, 1.5), (2.0, 3.5)]
    assert tracing.gaps(busy, -1.0, 4.0) == [(-1.0, 0.0), (1.5, 2.0),
                                             (3.5, 4.0)]


def test_summary_of_one_device():
    dev = {"/device:TPU:0": [("fusion.1", 1.0, 2.0), ("fusion.2", 2.0, 3.0),
                             ("fusion.1", 6.0, 7.0),
                             ("outside", 11.0, 12.0)]}
    host = [("bench.window", 0.0, 10.0), ("bench.feed", 3.0, 5.5),
            ("bench.step", 5.5, 8.0), ("bench.sync", 8.0, 10.0),
            ("PjitFunction", 3.5, 4.0)]
    s = tracing.summarize(dev, host, n_devices=1)
    assert s["window_s"] == pytest.approx(10.0)
    # busy: [1, 3] and [6, 7]; operations outside the window do not count
    assert s["busy_s"] == pytest.approx(3.0)
    ops = dict(s["device_ops"])
    assert ops == pytest.approx({"fusion.1": 2.0, "fusion.2": 1.0})
    assert list(ops) == ["fusion.1", "fusion.2"]
    # gaps: [0, 1] unannotated, [3, 6] mid 4.5 in feed, [7, 10] mid 8.5
    # in sync
    gaps = dict(s["idle_gaps"])
    assert gaps == pytest.approx({"bench.feed": 3.0, "bench.sync": 3.0,
                                  "host.unannotated": 1.0})


def test_nested_operations_count_once():
    """A loop's event spans its body's operations: the breakdown gives
    each its self time, and busy time is the union."""
    evs = [("while", 0.0, 10.0), ("fusion", 1.0, 3.0), ("fusion", 4.0, 5.0),
           ("copy", 12.0, 13.0)]
    assert tracing.self_times(evs) == pytest.approx(
        {"while": 7.0, "fusion": 3.0, "copy": 1.0})
    s = tracing.summarize({"/device:TPU:0": evs},
                          [("bench.window", 0.0, 20.0)], 1)
    assert s["busy_s"] == pytest.approx(11.0)
    assert sum(t for _, t in s["device_ops"]) == pytest.approx(11.0)


def test_innermost_annotation_names_a_gap():
    spans = sorted([("bench.solve", 0.0, 10.0), ("bench.submit", 4.0, 5.0)],
                   key=lambda e: e[1])
    assert tracing.name_gaps([(4.2, 4.4), (6.0, 7.0), (11.0, 12.0)],
                             spans) == ["bench.submit", "bench.solve",
                                        "host.unannotated"]


def test_busy_is_averaged_over_the_cell_chips():
    dev = {"/device:TPU:0": [("op", 0.0, 4.0)],
           "/device:TPU:1": [("op", 0.0, 2.0)]}
    host = [("bench.window", 0.0, 4.0)]
    assert tracing.summarize(dev, host, 2)["busy_s"] == pytest.approx(3.0)
    # a chip with no operation is idle all through
    assert tracing.summarize(dev, host, 3)["busy_s"] == pytest.approx(2.0)


def test_device_operations_are_named_by_name_and_shape():
    assert tracing.op_name("%fusion.2 = f32[1024,8,128]{0,1,2:T(8,128)S(1)}"
                           " fusion(%p0), kind=kLoop") == \
        "fusion.2 f32[1024,8,128]"
    assert tracing.op_name("%while.154 = (s32[]{:T(128)}, f32[32,512,768]"
                           "{2,1,0:T(8,128)}) while(%t)") == \
        "while.154 (s32[]"
    assert tracing.op_name("copy-start.3") == "copy-start.3"


def test_a_trace_without_the_window_is_refused():
    with pytest.raises(ValueError):
        tracing.summarize({}, [("bench.solve", 0.0, 1.0)], 1)


def test_recorded_cpu_trace(f32):
    """A real profiler trace: the window and the host annotations are
    read back from the ``.xplane.pb`` (the CPU has no device plane)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: jnp.cumsum(jnp.exp(a), axis=0))
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    cap = tracing.Capture()
    try:
        cap.start()
        with tracing.window(cap):
            with tracing.annotate("bench.solve"):
                f(x).block_until_ready()
        cap.stop()
        devices, host = tracing.read_events(cap.path)
        names = {n for n, _, _ in host}
        assert {"bench.window", "bench.solve"} <= names
        s = tracing.summarize(devices, host, 1)
        assert s["window_s"] > 0
        assert 0.0 <= s["busy_s"] <= s["window_s"]
    finally:
        cap.cleanup()
    assert cap.dir is None
