"""Host spans of the served path, read back from a profiler trace.

Every layer boundary of ``ClusterBatcher`` opens a ``repro.<name>`` span
(``repro.util.span``, a ``jax.profiler.TraceAnnotation``), so the spans
share the device trace's clock. A small async engine serves three cold
graphs in two flushes and one repeat (a result-cache hit) under
``jax.profiler.start_trace``; the tests read the ``.xplane.pb`` back with
``ProfileData`` and check which spans exist, how they nest, their
arguments, and that tracing changes no answer. The stable names of the
jitted programs are checked by lowering them.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_graph, degeneracy_peel, plan_graph
from repro.core import executor as ex
from repro.core import mis
from repro.core.dist import pow2_device_mesh
from repro.core.graph import path, random_arboric
from repro.serve.cluster_batcher import ClusterBatcher, ClusterRequest

K = 2
SPANS = {"admit", "plan", "degeneracy", "fingerprint", "rows", "flush",
         "assemble", "rank_wait", "submit", "harvest", "compile"}


class _Recording(ex.AsyncExecutor):
    """The async executor, recording each flush's input bytes and handle."""

    def __init__(self):
        super().__init__()
        self.nbytes, self.handles = [], []

    def submit(self, ell, ranks_p, elig_p, m_edges, *args, **kwargs):
        self.nbytes.append(sum(a.nbytes for a in (ell, ranks_p, elig_p,
                                                  m_edges)))
        handle = super().submit(ell, ranks_p, elig_p, m_edges, *args,
                                **kwargs)
        self.handles.append(handle)
        return handle


def _requests():
    """uids 0 and 1 share one bucket (a full flush at ``max_batch`` 2),
    uid 2 has a bucket of its own, uid 3 repeats uid 0's graph and key."""
    rng = np.random.default_rng(5)
    edges, _ = random_arboric(20, 2, rng)
    graphs = [build_graph(6, path(6)), build_graph(7, path(7)),
              build_graph(20, edges)]
    keys = [jax.random.PRNGKey(11 + i) for i in range(3)]
    return [(uid, graphs[uid % 3], keys[uid % 3]) for uid in range(4)]


def _serve():
    executor = _Recording()
    eng = ClusterBatcher(max_batch=2, num_samples=K, executor=executor)
    done = []
    for uid, graph, key in _requests()[:3]:
        done += eng.admit(ClusterRequest(uid=uid, graph=graph, key=key))
    done += eng.flush()
    uid, graph, key = _requests()[3]
    done += eng.admit(ClusterRequest(uid=uid, graph=graph, key=key))
    done += eng.flush()
    answers = {r.uid: (np.asarray(r.result.labels), int(r.result.cost))
               for r in done}
    return eng, executor, answers


def _read_spans(trace_dir):
    """``[name, start_ns, end_ns, line, args]`` of every ``repro.`` event,
    ``name`` without its prefix, in start order."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(max(files,
                                                  key=os.path.getmtime))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append([ev.name[len("repro."):], ev.start_ns,
                                ev.end_ns, (plane.name, line.name),
                                {k: v for k, v in ev.stats}])
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The same requests served untraced, then traced with every bucket
    program compiled afresh: ``(spans, engine, executor, answers_off,
    answers_on)``."""
    _, _, answers_off = _serve()
    ex._program_cache.clear()
    trace_dir = str(tmp_path_factory.mktemp("spans"))
    jax.profiler.start_trace(trace_dir)
    try:
        eng, executor, answers_on = _serve()
    finally:
        jax.profiler.stop_trace()
    return _read_spans(trace_dir), eng, executor, answers_off, answers_on


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def test_every_layer_span_is_recorded(served):
    spans = served[0]
    assert {s[0] for s in spans} == SPANS


@pytest.mark.parametrize("child, parent", [
    ("plan", "admit"), ("degeneracy", "plan"), ("fingerprint", "admit"),
    ("rows", "admit"), ("assemble", "flush"), ("rank_wait", "assemble"),
    ("submit", "flush"), ("compile", "submit")])
def test_spans_nest_inside_their_layer(served, child, parent):
    spans = served[0]
    children = _named(spans, child)
    assert children
    for name, start, end, line, _ in children:
        assert any(p[1] <= start and end <= p[2] and p[3] == line
                   for p in _named(spans, parent)), (name, start)


def test_one_plan_per_admission_and_rows_only_when_cold(served):
    spans, eng = served[0], served[1]
    assert eng.stats.cache_hits == 1 and eng.stats.cache_misses == 3
    uids = lambda name: sorted(s[4]["uid"] for s in _named(spans, name))
    # A cache hit is planned too: its fingerprint is taken from the plan.
    assert uids("admit") == uids("plan") == uids("fingerprint") \
        == [0, 1, 2, 3]
    assert uids("rows") == [0, 1, 2]
    assert len(_named(spans, "degeneracy")) == 4
    # One rank fetch per cold request, when its flush assembles.
    assert len(_named(spans, "rank_wait")) == 3


def test_submit_bytes_are_the_packed_inputs(served):
    spans, executor = served[0], served[2]
    assert [s[4]["bytes"] for s in _named(spans, "submit")] \
        == executor.nbytes
    assert len(executor.nbytes) == 2


def test_harvest_names_its_flush(served):
    spans, executor = served[0], served[2]
    flushes = _named(spans, "flush")
    assert [(s[4]["flush"], s[4]["graphs"]) for s in flushes] \
        == [(0, 2), (1, 1)]
    assert [h.flush for h in executor.handles] == [0, 1]
    assert [s[4]["flush"] for s in _named(spans, "assemble")] == [0, 1]
    harvests = _named(spans, "harvest")
    assert sorted(s[4]["flush"] for s in harvests) == [0, 1]
    for harvest in harvests:
        flush = flushes[harvest[4]["flush"]]
        assert harvest[1] >= flush[2]


def test_flush_span_carries_the_submitted_shape(served):
    spans, executor = served[0], served[2]
    flushes = _named(spans, "flush")
    assert len(flushes) == len(executor.handles) == 2
    for span, handle in zip(flushes, executor.handles):
        b, r, w = handle.shape
        args = span[4]
        assert (args["R"], args["W"]) == (r, w)
        assert args["graphs"] == len(handle.payload)
        assert args["g_pad"] * K == b
    assert [s[4]["g_pad"] for s in flushes] == [2, 1]


def test_compile_spans_name_the_bucket(served):
    spans = served[0]
    buckets = sorted((s[4]["R"], s[4]["W"]) for s in _named(spans,
                                                            "compile"))
    assert buckets == sorted({plan_graph(graph).bucket
                              for _, graph, _ in _requests()})


def test_answers_are_identical_with_the_profiler_on(served):
    off, on = served[3], served[4]
    assert sorted(off) == sorted(on) == [0, 1, 2, 3]
    for uid in off:
        assert (off[uid][0] == on[uid][0]).all()
        assert off[uid][1] == on[uid][1]
    assert (on[3][0] == on[0][0]).all()


def _module_name(lowered):
    return lowered.as_text().split("\n", 1)[0].split()[1]


@pytest.mark.parametrize("sharded", [False, True])
def test_bucket_program_is_named_for_its_function(sharded):
    mesh = pow2_device_mesh(1) if sharded else None
    fn = ex._build_program(K, False, False, mesh)
    b, r, w = 2 * K, 8, 4
    lowered = fn.lower(jax.ShapeDtypeStruct((b, r, w), jnp.int32),
                       jax.ShapeDtypeStruct((b, r + 1), jnp.int32),
                       jax.ShapeDtypeStruct((b, r + 1), jnp.bool_),
                       jax.ShapeDtypeStruct((b,), jnp.int32))
    assert _module_name(lowered) == "@jit_bucket_impl"


@pytest.mark.parametrize("samples", [1, 3])
def test_rank_program_is_named_rank_draw(samples):
    keys = jax.random.split(jax.random.PRNGKey(0), samples)
    if samples == 1:
        lowered = mis._perm_ranks_single_for(10).lower(keys[0])
    else:
        lowered = mis._perm_ranks_batch_for(10).lower(keys)
    assert _module_name(lowered) == "@jit_rank_draw"


def test_harvest_carries_the_swept_tiles(served):
    spans, eng, executor = served[0], served[1], served[2]
    harvests = {s[4]["flush"]: s[4] for s in _named(spans, "harvest")}
    for handle in executor.handles:
        args = harvests[handle.flush]
        assert (args["ell_tiles_swept"], args["ell_tiles_full"]) \
            == handle.ell_tiles
    assert eng.stats.ell_tiles_swept == sum(
        h.ell_tiles[0] for h in executor.handles)
    assert eng.stats.ell_tiles_full == sum(
        h.ell_tiles[1] for h in executor.handles)


def test_degeneracy_span_carries_the_peel_counts(served):
    spans, eng = served[0], served[1]
    plans = {s[4]["uid"]: s for s in _named(spans, "plan")}
    for uid, graph, _ in _requests():
        plan = plans[uid]
        inner = [s for s in _named(spans, "degeneracy")
                 if plan[1] <= s[1] and s[2] <= plan[2]]
        assert len(inner) == 1
        peel = degeneracy_peel(graph)
        assert (inner[0][4]["peel_rounds"], inner[0][4]["peel_single"]) \
            == (peel.rounds, peel.single)
    peels = [degeneracy_peel(graph) for _, graph, _ in _requests()]
    assert eng.stats.peel_rounds == sum(p.rounds for p in peels)
    assert eng.stats.peel_single == sum(p.single for p in peels) > 0


def test_rows_span_carries_the_artifacts_size(served):
    # nnz: the real ELL entries (two per eligible-induced edge); bytes: the
    # compact artifact with its K rank rows, summed into stats.rows_bytes.
    spans, eng = served[0], served[1]
    rows = {s[4]["uid"]: s[4] for s in _named(spans, "rows")}
    for uid, graph, _ in _requests()[:3]:
        plan = plan_graph(graph)
        nnz = 2 * len(plan.canonical_edges)
        assert rows[uid]["nnz"] == nnz
        assert rows[uid]["bytes"] == 8 * nnz + (plan.R + 1) \
            + 4 * K * (plan.R + 1)
    assert eng.stats.rows_bytes == sum(a["bytes"] for a in rows.values())
