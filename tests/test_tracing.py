"""The program's tracer (``repro.tracing``): query and prepare spans, the
level loop's device counters, and the map from the compiled engine's
operations to its named stages."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core import reference_bfs
from repro.core.bfs import (INF, PULL_CHUNK, BlestProblem,
                            level_counter_names, make_blest_bfs, make_engine)
from repro.core.bvss import build_bvss
from repro.core.policy import PrepareOptions, prepare
from repro.errors import ConfigError
from repro.graphs import from_edges, generators as gen, src_of_edges
from repro.serve import GraphSessionManager
from repro.serve.session_manager import EVENTS_KEPT


def _kron():
    """A small Kronecker graph, made undirected as the Graph500 one is:
    every reached vertex then has an edge, so the loop runs one level
    past the deepest (on a directed graph it may stop at that level)."""
    g = gen.rmat(9, 8, seed=3)
    src, dst = src_of_edges(g), g.indices.astype(np.int64)
    return from_edges(g.n, np.concatenate([src, dst]),
                      np.concatenate([dst, src]), dedup=True)


GRAPHS = {"kron": _kron, "grid": lambda: gen.grid2d(24, 24),
          "rgg": lambda: gen.rgg2d(600, seed=2)}
QUERY_CHILDREN = ("repro.query.admit", "repro.query.dispatch",
                  "repro.query.wait", "repro.query.copy",
                  "repro.query.unpermute", "repro.query.verify")


@pytest.fixture(scope="module", params=sorted(GRAPHS))
def graph(request):
    return GRAPHS[request.param]()


def _children(spans, parent):
    return [s for s in spans if s.parent == parent.span_id]


@pytest.mark.parametrize("direction", ["auto", "push"])
@pytest.mark.parametrize("engine", ["blest", "blest_lazy"])
def test_level_counters_match_the_reference(graph, engine, direction):
    pb = prepare(graph, options=PrepareOptions(engine=engine,
                                               direction=direction))
    for src in (0, graph.n // 2):
        lv = pb.levels(src)
        want = reference_bfs(graph, src)
        np.testing.assert_array_equal(lv, want)
        c = tracing.last("repro.query").counts
        assert c["bfs.levels"] == want[want != INF].max() + 1
        pulls = c["bfs.levels"] - c["bfs.push_levels"]
        assert 0 <= pulls <= c["bfs.pull_chunks"]
        assert c["bfs.pull_slots"] == PULL_CHUNK * c["bfs.pull_chunks"]
        assert (0 <= c["bfs.pull_slots"] - c["bfs.live_vss"]
                < PULL_CHUNK * max(pulls, 1))
        if direction == "push":
            assert c["bfs.push_levels"] == c["bfs.levels"]


def test_counted_engine_keeps_the_public_signature():
    g = GRAPHS["kron"]()
    p = BlestProblem.build(build_bvss(g))
    lv = make_engine(g, "blest", problem=p)(0)
    np.testing.assert_array_equal(np.asarray(lv), reference_bfs(g, 0))
    lv2, counts = make_blest_bfs(p, lazy=False, counters=True)(0)
    np.testing.assert_array_equal(np.asarray(lv2), np.asarray(lv))
    names = level_counter_names(len(counts))
    assert names == ("levels", "push_levels", "pull_slots", "live_vss",
                     "pull_chunks")
    with pytest.raises(ConfigError):
        make_engine(g, "csr_push", counters=True)


@pytest.mark.parametrize("verify", [0.0, 1.0])
def test_query_spans_share_one_request(verify):
    g = GRAPHS["kron"]()
    mgr = GraphSessionManager(verify_fraction=verify)
    mgr.open_session("g", g, max_batch=2)
    mgr.levels("g", 3)
    q = tracing.last("repro.query")
    mine = [s for s in tracing.snapshot() if s.request == q.request]
    kids = _children(mine, q)
    names = [s.name for s in kids]
    want = set(QUERY_CHILDREN) - ({"repro.query.verify"} if not verify
                                  else set())
    assert set(names) == want and len(names) == len(want)
    assert all(s.name == "repro.query" or s.parent == q.span_id
               for s in mine)
    for s in kids:
        assert q.start <= s.start <= s.end <= q.end
    assert q.counts["bfs.levels"] >= 1
    assert tracing.last("repro.query").request == q.request
    mgr.levels("g", 4)
    assert tracing.last("repro.query").request != q.request


def test_prepare_records_its_stages():
    sess = GraphSessionManager().open_session("g", GRAPHS["grid"](),
                                              max_batch=2)
    top = tracing.last("repro.prepare")
    kids = _children(tracing.snapshot(), top)
    assert [s.name for s in kids] == ["repro.prepare.ordering",
                                      "repro.prepare.bvss",
                                      "repro.prepare.engine"]
    for s in kids:
        assert top.start <= s.start <= s.end <= top.end
    assert sess.preprocess_s == top.seconds


def _loop_ops(hlo_text):
    comps, entry = tracing.parse_hlo(hlo_text)
    whiles = [i for i in comps[entry] if i.opcode == "while"]
    assert whiles
    return [i for w in whiles for c in w.control
            for name in tracing.scheduled(comps, c) for i in comps[name]
            if i.opcode in ("fusion", "custom-call")]


@pytest.mark.parametrize("use_kernels", [True, False])
@pytest.mark.parametrize("lazy", [False, True])
def test_every_loop_operation_has_a_stage(lazy, use_kernels):
    p = BlestProblem.build(build_bvss(gen.grid2d(40, 40)))
    fn = make_blest_bfs(p, lazy=lazy, use_kernels=use_kernels,
                        counters=True)
    text = fn.lower(jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()
    stages = tracing.stage_map(text)
    ops = _loop_ops(text)
    assert ops and all(stages.get(i.name) in tracing.BFS_STAGES for i in ops)
    assert set(stages.values()) == set(tracing.BFS_STAGES)


def test_stage_map_reads_scopes_fusions_and_neighbours():
    text = """HloModule m

%fused (p: s32[4]) -> s32[4] {
  %p = s32[4]{0} parameter(0)
  ROOT %a = s32[4]{0} add(%p, %p), metadata={op_name="jit(f)/bfs.finalize/while/body/bfs.update/add"}
}

%body (t: (s32[4], s32[])) -> (s32[4], s32[]) {
  %t = (s32[4]{0}, s32[]) parameter(0)
  %x = s32[4]{0} get-tuple-element(%t), index=0
  %c = s32[4]{0:T(128)} copy(s32[4]{0:T(128)} %x)
  %f = s32[4]{0} fusion(%c), kind=kLoop, calls=%fused
  %k = s32[4]{0} custom-call(%f), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/bfs.finalize/while/body/bfs.expand/bvss_pull"}
  %n = s32[] get-tuple-element(%t), index=1
  ROOT %r = (s32[4]{0}, s32[]) tuple(%k, %n)
}

%cond (t: (s32[4], s32[])) -> pred[] {
  %t = (s32[4]{0}, s32[]) parameter(0)
  ROOT %lt = pred[] constant(false), metadata={op_name="jit(f)/bfs.finalize/while/cond/lt"}
}

ENTRY %main (a: s32[4]) -> s32[4] {
  %a = s32[4]{0} parameter(0)
  %init = s32[4]{0} negate(%a), metadata={op_name="jit(f)/neg"}
  %z = s32[] constant(0)
  %tup = (s32[4]{0}, s32[]) tuple(%init, %z)
  %w = (s32[4]{0}, s32[]) while(%tup), condition=%cond, body=%body, metadata={op_name="jit(f)/bfs.finalize/while"}
  ROOT %out = s32[4]{0} get-tuple-element(%w), index=0
}
"""
    stages = tracing.stage_map(text)
    assert stages["f"] == "bfs.update"          # from its fused ops
    assert stages["k"] == "bfs.expand"          # innermost scope
    assert stages["c"] == "bfs.update"          # from its user
    assert stages["lt"] == "bfs.finalize"
    assert stages["w"] == "bfs.finalize"
    assert "init" not in stages                 # the loop's initial state


def test_tracer_ring_counters_and_nesting():
    t = tracing.Tracer(capacity=8)
    with t.query() as q:
        with t.span("a"):
            with t.query() as inner:
                t.record({"x": 2})
        t.record({"x": 1})
    assert inner is q and q.counts == {"x": 3}
    assert t.counter("x") == 3
    assert [s.name for s in t.snapshot()] == ["a", "repro.query"]

    def work():
        for _ in range(200):
            t.count("n")
            with t.span("s"):
                pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=60)
    assert not any(th.is_alive() for th in threads)
    assert t.counter("n") == 800
    assert len(t.snapshot()) == 8


def test_manager_events_are_bounded_and_counted():
    mgr = GraphSessionManager()
    for _ in range(EVENTS_KEPT + 10):
        mgr._event("verify-pass", name="g", source=0)
    assert len(mgr.events) == EVENTS_KEPT
    assert mgr.stats()["verified"] == EVENTS_KEPT + 10
