"""The delta-stepping SSSP engine (DESIGN §2.9) against SciPy's Dijkstra
(``kernels/ref.sssp_ref``) on seeded random weights: its relaxation
pulls the live queue in chunks of ``PULL_CHUNK`` slots at every live VSS
count around the chunk edges, its counters account the chunks and the
finite pairs, its update kernel gives bitwise the distances of the jnp
scatter, the manager's ``sssp`` verb answers in caller ids under one
traced query, and every operation of its loops maps to a named stage."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.analytics import sssp as sssp_mod
from repro.analytics.sssp import SSSP_COUNTERS, make_sssp
from repro.core.bfs import PULL_CHUNK, BlestProblem
from repro.core.bvss import build_bvss, build_weight_plane, \
    weight_plane_to_device
from repro.errors import GraphValidationError
from repro.graphs import from_edges, generators as gen, src_of_edges
from repro.kernels.ref import sssp_ref
from repro.serve import GraphSession, GraphSessionManager
from conftest import require_devices
from test_pull_chunks import LIVE, fan, live_counts

C = PULL_CHUNK


def kernel3_weights(g, rng) -> np.ndarray:
    """Graph500 Kernel 3 weights: one uniform float32 of (0, 1) per
    undirected edge, the same on both of its arcs."""
    src = src_of_edges(g).astype(np.int64)
    dst = g.indices.astype(np.int64)
    key = np.minimum(src, dst) * g.n + np.maximum(src, dst)
    uniq, inv = np.unique(key, return_inverse=True)
    w = rng.random(len(uniq), dtype=np.float32)
    return np.where(w > 0, w, np.float32(0.5))[inv]


def assert_close(got, want):
    """Same reached set, +inf exactly where unreached, reached distances
    within float32 accumulation (2^-17 relative)."""
    np.testing.assert_array_equal(np.isinf(got), np.isinf(want))
    np.testing.assert_allclose(got[np.isfinite(want)],
                               want[np.isfinite(want)], rtol=2.0 ** -17)


@pytest.fixture(scope="module", params=sorted(LIVE))
def case(request):
    g = fan(request.param)
    b = build_bvss(g)
    w = kernel3_weights(g, np.random.default_rng(LIVE[request.param]))
    counts = live_counts(b, sssp_ref(g, [0], np.ones(g.m))[0])
    assert b.num_vss % C, "queue capacity already whole chunks"
    return request.param, g, b, w, counts


def _bits(x) -> np.ndarray:
    return np.asarray(x).view(np.uint32)


def test_relaxation_chunks_match_the_reference(case, monkeypatch):
    """Δ above every distance: one bucket whose rounds are the BFS levels
    of the fan, so round r pulls exactly the live VSSs of level r - 1.
    ``finite_rows`` is the dense count of the finite pairs with a real
    row that the jnp scatter saw; the update kernel, over the live pairs
    alone, gives bitwise the same distances and counters."""
    name, g, b, w, counts = case
    p = BlestProblem.build(b)
    wplane = weight_plane_to_device(build_weight_plane(g, w))
    seen = []

    def counted_scatter(dist, rows, vals, n_pairs):
        jax.debug.callback(lambda r, v: seen.append(
            int((np.isfinite(v).any(axis=0) & (r < g.n)).sum())), rows, vals)
        return dist.at[:, rows].min(vals)

    monkeypatch.setattr(sssp_mod, "_xla_scatter", counted_scatter)
    fn = make_sssp(p, wplane, 1, use_kernel=False, counters=True)
    dist, c = fn(jnp.asarray([0], jnp.int32), jnp.float32(4.0))
    assert_close(np.asarray(dist)[0], sssp_ref(g, [0], w)[0])
    kdist, kc = make_sssp(p, wplane, 1, counters=True)(
        jnp.asarray([0], jnp.int32), jnp.float32(4.0))
    np.testing.assert_array_equal(_bits(kdist), _bits(dist))
    assert list(np.asarray(kc)) == list(np.asarray(c))
    rounds, buckets, slots, live, chunks, finite = (int(x) for x in c)
    assert len(seen) == chunks and finite == sum(seen)
    assert (finite > 0) == (live > 0) and finite <= 128 * live
    assert buckets == 1
    assert rounds == len(counts) + 1      # the last frontier pulls nothing
    assert live == sum(counts)
    assert chunks == sum(-(-x // C) for x in counts)
    assert slots == C * chunks
    assert 0 <= slots - live <= (C - 1) * rounds
    if name == "full":
        assert max(counts) == b.num_vss


def _kron():
    """A small undirected Kronecker graph, as Graph500's Kernel 1 makes
    it, with Kernel 3 weights."""
    g = gen.rmat(9, 8, seed=5)
    src, dst = src_of_edges(g), g.indices.astype(np.int64)
    g = from_edges(g.n, np.concatenate([src, dst]),
                   np.concatenate([dst, src]), dedup=True)
    return g, kernel3_weights(g, np.random.default_rng(11))


@pytest.mark.parametrize("mesh", ["single", "sharded"])
def test_update_kernel_is_bitwise_the_jnp_scatter(mesh):
    """On the Kernel 3 Kronecker graph, a cohort of three sources: the
    engine with its kernels gives bitwise the distances of the engine
    with the jnp forms, on one device and on each shard of a 1-D mesh."""
    g, w = _kron()
    deg = np.diff(g.indptr)
    srcs = [3, int(np.argmax(deg)), int(np.flatnonzero(deg)[-1])]
    kw = {}
    if mesh == "sharded":
        require_devices(2)
        from repro.distributed.bfs_dist import bfs_mesh
        kw = {"mesh": bfs_mesh(2)}
    got = [GraphSession(g, weights=w, max_batch=3, use_kernel=k,
                        **kw).sssp_batch(srcs) for k in (True, False)]
    np.testing.assert_array_equal(got[0].view(np.uint64),
                                  got[1].view(np.uint64))
    assert_close(got[0], sssp_ref(g, srcs, w))


def test_manager_sssp_caller_ids_one_query(monkeypatch):
    g, w = _kron()
    means = []
    mean = sssp_mod.default_delta
    monkeypatch.setattr("repro.serve.graph_session.default_delta",
                        lambda x: means.append(1) or mean(x))
    mgr = GraphSessionManager()
    mgr.open_session("g", g, max_batch=2, weights=w)
    deg = np.diff(g.indptr)
    roots = [int(np.argmax(deg)), int(np.flatnonzero(deg)[0]), g.n - 1]
    for src in roots:
        got = mgr.sssp("g", src)
        assert got.dtype == np.float64 and got.shape == (g.n,)
        assert_close(got, sssp_ref(g, [src], w)[0])
        q = tracing.last("repro.query")
        kids = [s.name for s in tracing.snapshot()
                if s.parent == q.span_id]
        assert kids == ["repro.query.admit", "repro.query.dispatch",
                        "repro.query.wait", "repro.query.copy",
                        "repro.query.unpermute"]
        c = {k[len("sssp."):]: v for k, v in q.counts.items()}
        assert set(c) == set(SSSP_COUNTERS)
        assert c["rounds"] >= c["buckets"] >= 1
        assert c["pull_slots"] == C * c["pull_chunks"]
        assert 0 <= c["pull_slots"] - c["live_vss"] <= \
            (C - 1) * c["rounds"]
    assert len(means) == 1                # Δ once per session
    with pytest.raises(GraphValidationError):
        mgr.sssp("g", g.n)
    mgr.close_session("g")


def test_every_sssp_loop_operation_has_a_stage():
    g, w = _kron()
    p = BlestProblem.build(build_bvss(g))
    fn = make_sssp(p, weight_plane_to_device(build_weight_plane(g, w)), 1,
                   counters=True).compile()
    text = fn.fn.as_text()
    stages = tracing.stage_map(text)
    comps, entry = tracing.parse_hlo(text)
    loops = [n for i in comps[entry] if i.opcode == "while"
             for c in i.control for n in tracing.scheduled(comps, c)]
    ops = [i for n in loops for i in comps[n]
           if i.opcode in ("fusion", "custom-call")]
    assert ops and all(stages.get(i.name) in tracing.SSSP_STAGES
                       for i in ops)
    assert set(stages.values()) == set(tracing.SSSP_STAGES)
    # the update kernel (interpreted here) lies in sssp.update alone, so
    # that the min-plus roofline's sssp.expand gains nothing of it
    kernel = [i for i in ops if "scatter_min" in i.op_name]
    assert kernel and {stages[i.name] for i in kernel} == {"sssp.update"}
    dist, _ = fn(jnp.asarray([3], jnp.int32), jnp.float32(0.5))
    assert_close(np.asarray(jax.device_get(dist))[0],
                 sssp_ref(g, [3], w)[0])


def test_weighted_prepare_records_the_plane_span():
    """``repro.prepare.weights`` is a sibling of ``.bvss``, opened only
    on a weighted graph, so an unweighted prepare records the same
    stages as before it."""
    g, w = _kron()
    for weights, want in ((None, ["repro.prepare.ordering",
                                  "repro.prepare.bvss",
                                  "repro.prepare.engine"]),
                          (w, ["repro.prepare.ordering",
                               "repro.prepare.bvss",
                               "repro.prepare.weights",
                               "repro.prepare.engine"])):
        GraphSessionManager().open_session("g", g, weights=weights)
        top = tracing.last("repro.prepare")
        kids = [s for s in tracing.snapshot() if s.parent == top.span_id]
        assert [s.name for s in kids] == want
        assert all(top.start <= s.start <= s.end <= top.end for s in kids)
