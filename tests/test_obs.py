"""Spans and counters inside the program (``repro.obs``).

* self time is a span's duration less what its child spans cover (on a
  clock the test advances by hand, so nothing here reads the wall clock);
* JAX's compiles count under the innermost open span, and only once;
* ``ServedQuery.busy_wait_s`` splits a request's wait into the part the
  server was busy and the part it sat idle (none: an idle server flushes
  at once);
* ``PerfModel.predict_rows`` reads back whole buckets, so a new row count
  compiles nothing;
* a model-backed ``serve()`` opens every span of the layer table;
* served results do not depend on whether a profiler trace is running;
* ``solve.subqs`` counts the subQs of the requests actually solved and
  ``runtime.requests`` what ``step_round`` serviced; neither opens a span.
"""
import jax
import numpy as np
import pytest

from repro import obs
from repro.core.models.perf_model import NONDECISION_DIM
from repro.core.moo.hmooc import HMOOCConfig
from repro.queryengine.workloads import StreamRequest, make_benchmark
from repro.serve import (OptimizerServer, RuntimeSession, ServerConfig,
                         ServiceTimeModel, TuningService)

CFG = HMOOCConfig(n_c_init=16, n_clusters=4, n_p_pool=48, n_c_enrich=12,
                  max_bank=12, seed=3)
WEIGHTS = (0.9, 0.1)
CLOCK = ServiceTimeModel(flush_points=((1, 0.05), (8, 0.2)), round_s=0.01)


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _run_tree(node, clock):
    name, work, children = node
    with obs.span(name):
        clock.t += work
        for child in children:
            _run_tree(child, clock)


def _expected(node, acc):
    """{name: [calls, total, self]} of a span tree; returns its total."""
    name, work, children = node
    total = work + sum(_expected(c, acc) for c in children)
    e = acc.setdefault(name, [0, 0.0, 0.0])
    e[0] += 1
    e[1] += total
    e[2] += work
    return total


@pytest.mark.parametrize("tree", [
    ("a", 3.0, []),
    ("a", 1.0, [("b", 2.0, []), ("c", 4.0, [("d", 8.0, [])])]),
    ("a", 0.0, [("b", 1.0, []), ("b", 2.0, [("c", 0.5, [])])]),
    ("a", 1.0, [("a", 2.0, [("b", 4.0, [])])]),
], ids=["leaf", "nested", "siblings_share_a_name", "recursive"])
def test_self_time_is_duration_less_child_spans(tree, monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(obs, "_perf_counter", clock)
    with obs.record() as rec:
        _run_tree(tree, clock)
    want = {}
    _expected(tree, want)
    assert rec.spans == want
    assert not rec._open


def test_no_record_keeps_no_books():
    with obs.record() as rec:
        pass
    with obs.span("repro.test"):           # after the record was closed
        obs.count("repro.test.n")
    assert rec.spans == {} and not rec.counters


def test_compiles_count_under_the_innermost_span():
    f = jax.jit(lambda x: x * 2.0 + 1.0)
    with obs.record() as rec:
        with obs.span("x"):
            with obs.span("y"):
                f(np.ones(3, np.float32))
            f(np.ones(5, np.float32))
        assert rec.counter("compiles@y") == 1
        assert rec.counter("compiles@x") == 1
        assert rec.counter("compile_s@y") > 0
        with obs.span("x"):
            f(np.ones(3, np.float32))       # the same shapes again
            f(np.ones(5, np.float32))
    assert rec.counter("compiles@x") == 1 and rec.counter("compiles@y") == 1
    assert rec.counter("compiles@" + obs.OUTSIDE) == 0


def _requests(arrivals, n_first=0):
    queries = make_benchmark("tpch")[n_first:n_first + len(arrivals)]
    return [StreamRequest(rid=i, query=q, arrival_s=a)
            for i, (q, a) in enumerate(zip(queries, arrivals))]


def _served(max_batch, arrivals):
    srv = OptimizerServer(config=ServerConfig(max_batch=max_batch,
                                              clock=CLOCK),
                          weights=WEIGHTS, cfg=CFG)
    return {s.rid: s for s in srv.serve(_requests(arrivals))}


def test_busy_wait_splits_the_wait_on_a_modelled_clock():
    # r0 finds an idle server and is flushed at once; r1 arrives while
    # r0's flush runs and waits for it and for one round; r2 arrives long
    # after everything finished.
    out = _served(1, [0.0, 0.001, 30.0])
    for s in out.values():
        wait = s.admitted_s - s.arrival_s
        assert 0.0 <= s.busy_wait_s <= wait
    assert out[0].admitted_s == out[0].arrival_s
    assert out[0].busy_wait_s == 0.0 and out[2].busy_wait_s == 0.0
    assert out[2].admitted_s == out[2].arrival_s
    assert out[1].busy_wait_s == pytest.approx(
        out[1].admitted_s - out[1].arrival_s)
    assert out[1].busy_wait_s > 0.0
    assert [s.flush_id for s in out.values()] == [0, 1, 2]


def test_batcher_hold_on_an_idle_server_is_not_busy_wait():
    # Two requests, a batch of 8: the idle server flushes the first at its
    # arrival instead of holding it for company; the second arrives during
    # that flush and waits only while the server is busy.
    out = _served(8, [0.0, 0.01])
    assert out[0].admitted_s == out[0].arrival_s
    assert out[0].busy_wait_s == 0.0
    assert out[1].admitted_s == out[1].arrival_s \
        or out[1].admitted_s >= out[0].compiled_s
    assert out[1].busy_wait_s == pytest.approx(
        out[1].admitted_s - out[1].arrival_s)
    assert [s.flush_id for s in out.values()] == [0, 1]
    tr = out[0].trace
    assert tr.counter("admission.flush.idle") >= 1
    assert sum(tr.counter(f"admission.flush.{r}")
               for r in ("idle", "full", "session")) == 2


@pytest.fixture(scope="module")
def models(smoke_perf_models):
    return smoke_perf_models["subq"], smoke_perf_models["qs"]


def _model_server(models):
    msub, mqs = models
    return OptimizerServer(
        config=ServerConfig(max_batch=3, clock=CLOCK),
        tuning=TuningService(model=msub, cfg=CFG),
        session=RuntimeSession(model_subq=msub, model_qs=mqs,
                               weights=WEIGHTS))


# Six distinct TPC-H templates, so every flush solves.
ARRIVALS = [0.0, 0.01, 0.02, 0.4, 0.41, 0.42]


def test_model_backed_serve_opens_every_span(models):
    srv = _model_server(models)
    served = srv.serve(_requests(ARRIVALS, n_first=2))
    st = srv.last_run
    tr = st.trace
    assert all(s.trace is tr for s in served)
    flushes = st.n_micro_batches
    assert flushes >= 2
    for name in ("repro.serve.flush", "repro.admission.compose",
                 "repro.runtime.admit", "repro.solve.lookup",
                 "repro.solve.hmooc.assign", "repro.solve.finish"):
        assert tr.calls(name) == flushes, name
    assert tr.counter("solve.solved") == len(ARRIVALS)
    assert 1 <= tr.calls("repro.solve.hmooc.banks") <= flushes
    assert tr.calls("repro.solve.rows") == \
        tr.calls("repro.solve.hmooc.banks") + flushes
    for name in ("repro.runtime.candidates", "repro.runtime.score",
                 "repro.runtime.pick", "repro.runtime.aqe"):
        assert tr.calls(name) == st.rounds, name
    assert tr.calls("repro.serve.round") >= st.rounds > 0
    assert tr.calls("repro.runtime.realize") >= 1
    for kind in ("subq", "qs"):
        for step in ("featurize", "pad", "dispatch", "readback"):
            assert tr.calls(f"repro.model.{step}.{kind}") >= 1, (step, kind)
        assert tr.counter(f"model.dispatches.{kind}") >= \
            tr.calls(f"repro.model.dispatch.{kind}")
        assert tr.counter(f"model.rows.{kind}") > 0
        assert tr.counter(f"model.graphs.{kind}") > 0
    # Self times add up to the top-level spans, which are all the server's.
    top = tr.total_s("repro.serve.flush") + tr.total_s("repro.serve.round")
    assert sum(tr.self_s(n) for n in tr.spans) == pytest.approx(top)
    assert all(not k.endswith("@" + obs.OUTSIDE) for k in tr.counters)


def _outputs(s):
    return (s.ct.theta_c, s.ct.theta_p_sub, s.ct.theta_s_sub, s.ct.front,
            s.result.theta_p_eff, s.result.theta_s_eff, s.result.final_join,
            s.result.sim.ana_latency, s.result.sim.actual_latency)


def test_predict_rows_compiles_nothing_for_a_new_row_count(models):
    msub, _ = models
    n = 64                                   # every count below is bucket 64
    rng = np.random.default_rng(5)
    emb = rng.normal(size=(n, msub.cfg.gtn.d_model)).astype(np.float32)
    theta = rng.uniform(size=(n, msub.cfg.theta_dim)).astype(np.float32)
    nond = rng.uniform(size=(n, NONDECISION_DIM)).astype(np.float32)
    msub.predict_rows(emb, theta, nond)      # the bucket's head compiles here
    with obs.record() as rec:
        got = [msub.predict_rows(emb[:c], theta[:c], nond[:c])
               for c in range(1, n + 1)]
    assert rec.counter("model.dispatches.subq") == n
    assert not {k: v for k, v in rec.counters.items()
                if k.startswith("compiles@") and v}
    # Bit for bit the rows of a slice taken on the device before the
    # read-back.
    for c, rows in enumerate(got, 1):
        def pad(a):
            return np.concatenate([a[:c], np.zeros((n - c, a.shape[1]),
                                                   np.float32)])
        z = msub._head(msub.params, pad(emb), pad(theta), pad(nond))
        np.testing.assert_array_equal(rows, msub.from_z(np.asarray(z[:c])))


def test_results_bit_identical_under_a_profiler_trace(models, tmp_path):
    reqs = _requests(ARRIVALS, n_first=8)
    plain = _model_server(models).serve(reqs)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = _model_server(models).serve(reqs)
    finally:
        jax.profiler.stop_trace()
    for a, b in zip(plain, traced):
        assert (a.status, a.flush_id) == (b.status, b.flush_id)
        for x, y in zip(_outputs(a), _outputs(b)):
            np.testing.assert_array_equal(x, y)


def test_solve_subqs_counts_the_subqs_of_solved_requests(models):
    msub, _ = models
    svc = TuningService(model=msub, cfg=CFG)
    a, b, c, d = (make_benchmark("tpch")[i] for i in (8, 4, 2, 6))
    assert len({a.n_subqs, b.n_subqs, c.n_subqs}) == 3
    with obs.record() as rec:
        svc.tune_batch([a, b, a])          # a again in the same run
        svc.tune_batch([b, c])             # b from the response cache
        svc.tune_batch([d], degraded=[True])
    assert svc.totals.n_solved == 3
    assert rec.counter("solve.solved") == 3
    assert rec.counter("solve.subqs") == a.n_subqs + b.n_subqs + c.n_subqs


def test_runtime_requests_counts_what_step_round_services(models):
    msub, mqs = models
    qs = make_benchmark("tpch")[:3]
    cts = TuningService(model=msub, cfg=CFG).tune_batch(qs)
    session = RuntimeSession(model_subq=msub, model_qs=mqs, weights=WEIGHTS)
    serviced = []
    with obs.record() as rec:
        for q, ct in zip(qs, cts):
            session.admit(q, ct)
        while n := session.step_round():
            serviced.append(n)
    assert len(serviced) > 1
    assert rec.counter("runtime.requests") == sum(serviced)
    # One per LQP or QS request sent; the pruned ones are never serviced.
    done = session.realize(session.retire_ready())
    assert sum(serviced) == sum(r.lqp_requests_sent + r.qs_requests_sent
                                for r in done)


def test_new_counters_open_no_span(models, monkeypatch):
    reqs = _requests(ARRIVALS, n_first=8)
    _model_server(models).serve(reqs)      # fills the models' embedding memo
    counted = _model_server(models).serve(reqs)[0].trace
    new = ("solve.subqs", "runtime.requests")
    count = obs.count
    monkeypatch.setattr(obs, "count",
                        lambda name, n=1: name in new or count(name, n))
    uncounted = _model_server(models).serve(reqs)[0].trace
    assert {k: v[0] for k, v in counted.spans.items()} == \
        {k: v[0] for k, v in uncounted.spans.items()}
    assert counted.counter("solve.subqs") == \
        sum(r.query.n_subqs for r in reqs)
    assert counted.counter("runtime.requests") > 0
    assert not any(uncounted.counter(k) for k in new)
