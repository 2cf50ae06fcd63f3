"""``trace_reduce.reduce`` on a hand-made trace, where every number can be
worked out by hand."""
import pytest

from chipbench import trace_reduce as T

MS = 1_000_000   # ns


def _trace():
    devices = {"/device:TPU:0": {
        "modules": [("jit__head_fn(3)", 10 * MS, 14 * MS),
                    ("jit__embed_batch", 30 * MS, 40 * MS),
                    ("jit__head_fn(7)", 60 * MS, 61 * MS),
                    ("jit__head_fn", 200 * MS, 210 * MS)],     # after the window
        "ops": [("fusion.1", 10 * MS, 12 * MS),
                ("dot.2", 11 * MS, 14 * MS),                   # overlaps fusion.1
                ("convolution", 30 * MS, 40 * MS),
                ("dot.2", 60 * MS, 61 * MS),
                ("dot.2", 200 * MS, 210 * MS)]}}
    spans = [("chipbench.window", 5 * MS, 105 * MS),
             ("chipbench.tune_batch", 8 * MS, 50 * MS),
             ("chipbench.embed_many.subq", 20 * MS, 45 * MS),
             ("chipbench.step_round", 55 * MS, 100 * MS),
             ("chipbench.realize", 70 * MS, 95 * MS)]
    return devices, spans


def test_reduce_by_hand():
    r = T.reduce(*_trace())
    assert r["window_s"] == pytest.approx(0.100)
    # Busy: [10, 14] + [30, 40] + [60, 61] ms = 15 ms.
    assert r["busy_s"] == pytest.approx(0.015)
    assert r["programs"] == {
        "jit__head_fn": {"s": pytest.approx(0.005), "calls": 2},
        "jit__embed_batch": {"s": pytest.approx(0.010), "calls": 1}}
    assert r["device_ops"] == [["convolution", pytest.approx(0.010)],
                               ["dot.2", pytest.approx(0.004)],
                               ["fusion.1", pytest.approx(0.002)]]
    # Idle gaps, each named by the span open at its middle: [5, 10] none
    # (7.5 is before tune_batch), [14, 30] embed_many (22), [40, 60] none
    # (50 is where tune_batch ends), [61, 105] realize (83).
    assert r["idle_gaps"] == [["chipbench.realize", pytest.approx(0.044)],
                              ["none", pytest.approx(0.020)],
                              ["chipbench.embed_many.subq",
                               pytest.approx(0.016)],
                              ["none", pytest.approx(0.005)]]
    assert r["host_spans_s"] == {
        "chipbench.tune_batch": pytest.approx(0.042),
        "chipbench.embed_many.subq": pytest.approx(0.025),
        "chipbench.step_round": pytest.approx(0.045),
        "chipbench.realize": pytest.approx(0.025)}


def test_top_limits_the_lists():
    r = T.reduce(*_trace(), top=1)
    assert len(r["device_ops"]) == 1 and len(r["idle_gaps"]) == 1


def test_needs_the_window_and_a_device():
    devices, spans = _trace()
    with pytest.raises(ValueError):
        T.reduce(devices, spans[1:])
    with pytest.raises(ValueError):
        T.reduce({}, spans)


def test_load_reads_spans_of_a_recorded_trace(tmp_path):
    """A trace recorded here (on the CPU, so it has no device plane): the
    benchmark's host spans come back with their nesting and lengths."""
    import time

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(T.WINDOW):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("chipbench.step"):
                f(x).block_until_ready()
                time.sleep(0.02)
    jax.profiler.stop_trace()
    devices, spans = T.load(T.find_trace(str(tmp_path)))
    assert devices == {}
    (w,) = [s for s in spans if s[0] == T.WINDOW]
    steps = [s for s in spans if s[0] == "chipbench.step"]
    assert len(steps) == 2
    for _, s, e in steps:
        assert w[1] <= s < e <= w[2] and e - s >= 0.02e9
    with pytest.raises(ValueError, match="no device plane"):
        T.reduce(devices, spans)
