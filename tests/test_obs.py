"""Host spans of the search and serving path (``repro.obs``) in the
profiler's own trace: one ``Searcher.search`` and one ``ServingLoop``
request traced on the CPU, the trace reduced with the benchmark's
``bench.tracing.from_xplane``, and the names, the nesting and the
request ids checked against docs/serving.md's table."""
import gc
import os
import sys

import jax
import numpy as np
import pytest

from repro.api import ICQConfig, icq_session
from repro.obs import span
from repro.serve import ServingLoop, Tenant

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import tracing  # noqa: E402

# child -> the spans that may hold it, innermost, on the same line
PARENTS = {
    "repro.embed": {"repro.search"},
    "repro.engine.search": {"repro.search", "repro.serve.flush"},
    "repro.engine.pad": {"repro.engine.search"},
    "repro.engine.dispatch": {"repro.engine.search"},
    "repro.engine.wait": {"repro.engine.search"},
    "repro.engine.assemble": {"repro.engine.search"},
    "repro.serve.embed": {"repro.serve.submit"},
    "repro.serve.enqueue": {"repro.serve.submit"},
    "repro.serve.pad": {"repro.serve.flush"},
    "repro.serve.copy": {"repro.serve.flush"},
    "repro.serve.deliver": {"repro.serve.flush"},
    "repro.search": {None},
    "repro.serve.submit": {None},
    "repro.serve.flush": {None},
    "repro.serve.idle": {None},
}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One direct search (3 rows, padded to the loop's tile of 4) and
    one served 1-row request, traced after a warm-up."""
    rng = np.random.default_rng(0)
    sess = icq_session(ICQConfig().with_overrides(
        {"train.d": 16, "train.num_codebooks": 4,
         "train.codebook_size": 16, "train.epochs": 1}))
    sess.fit(rng.standard_normal((256, 32)).astype(np.float32),
             key=jax.random.PRNGKey(0))
    searcher = sess.index(rng.standard_normal((400, 32)).astype(np.float32))
    q = rng.standard_normal((3, 32)).astype(np.float32)
    out = str(tmp_path_factory.mktemp("trace"))
    # a window long enough that even a loaded worker waits it out (the
    # `repro.serve.idle` span) inside the traced window
    with ServingLoop(Tenant.from_searcher("s", searcher), window_ms=20.0,
                     tile=4) as loop:
        loop.warm()
        loop.search(q[:1], timeout=60)
        np.asarray(searcher.search(q).indices)
        with jax.profiler.trace(out):
            np.asarray(searcher.search(q).indices)
            fut = loop.submit(q[:1])
            fut.result(timeout=60)
            gc.collect()
    path = tracing.find_xplane(out)
    return tracing.from_xplane(path), path


def _lines(trace):
    """{line: [(name, start, end), ...]} of the host lines holding
    ``repro.`` spans."""
    out = {}
    for p, plane in enumerate(trace["planes"]):
        for i, line in enumerate(plane["lines"]):
            evs = [(n, s, s + d) for n, s, d in line["events"]
                   if n.startswith("repro.")]
            if evs:
                out[(p, i)] = evs
    return out


def _parent(ev, evs):
    """The innermost other span of the line that holds ``ev``."""
    name, s, e = ev
    holders = [o for o in evs if o is not ev and o[1] <= s and e <= o[2]
               and (o[2] - o[1]) > (e - s)]
    return min(holders, key=lambda o: o[2] - o[1])[0] if holders else None


def test_every_span_of_the_table_appears_once_or_more(traced):
    trace, _ = traced
    names = {n for evs in _lines(trace).values() for n, _, _ in evs}
    assert set(PARENTS) <= names
    assert "repro.gc" in names


def test_spans_nest_as_the_table_says(traced):
    trace, _ = traced
    seen = set()
    for evs in _lines(trace).values():
        for ev in evs:
            if ev[0] in PARENTS:
                assert _parent(ev, evs) in PARENTS[ev[0]], ev
                seen.add((ev[0], _parent(ev, evs)))
    # the engine runs under both callers
    assert ("repro.engine.search", "repro.search") in seen
    assert ("repro.engine.search", "repro.serve.flush") in seen


def test_submit_and_worker_spans_sit_on_their_own_lines(traced):
    trace, _ = traced
    lines = _lines(trace)
    where = {}
    for k, evs in lines.items():
        for n, _, _ in evs:
            where.setdefault(n, set()).add(k)
    worker = where["repro.serve.flush"]
    assert len(worker) == 1
    assert where["repro.serve.idle"] == worker
    assert where["repro.serve.submit"].isdisjoint(worker)
    assert where["repro.search"] == where["repro.serve.submit"]


def test_a_request_id_links_its_submit_and_deliver_spans(traced):
    _, path = traced
    pd = jax.profiler.ProfileData.from_file(path)
    rids = {"repro.serve.submit": [], "repro.serve.deliver": []}
    attrs = {}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in rids:
                    rids[e.name].append(dict(e.stats)["rid"])
                if e.name in ("repro.serve.flush", "repro.engine.search"):
                    attrs[e.name] = dict(e.stats)
    assert len(rids["repro.serve.submit"]) == 1
    assert rids["repro.serve.deliver"] == rids["repro.serve.submit"]
    assert attrs["repro.serve.flush"]["rows"] == 1
    assert attrs["repro.serve.flush"]["tile"] == 4
    assert attrs["repro.engine.search"]["level"] == "full"
    # the jnp engine runs no fused kernel
    assert attrs["repro.engine.search"]["crude_cols"] == 0
    assert attrs["repro.engine.search"]["refine_cols"] == 0
    assert attrs["repro.engine.search"]["shards"] == 1      # no mesh


def test_a_span_name_is_built_once():
    with span("test.quiet", rows=1):
        pass
    from repro import obs
    first = obs._NAMES["test.quiet"]
    with span("test.quiet"):
        pass
    assert obs._NAMES["test.quiet"] is first


def test_gc_spans_are_hooked_only_while_a_loop_runs():
    class _Engine:
        query_tile = None
        index = None

    loop = ServingLoop(Tenant(name="t", engine=_Engine()), tile=2)
    assert loop._gc_span not in gc.callbacks
    loop.start()
    assert gc.callbacks.count(loop._gc_span) == 1
    loop.close()
    assert loop._gc_span not in gc.callbacks


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for val in eqn.params.values():
            for sub in val if isinstance(val, (list, tuple)) else [val]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _pallas_calls(inner)


@pytest.mark.parametrize("kind", ["two-step", "ivf"])
def test_each_pass_contracts_over_its_own_codebooks(kind, tmp_path):
    """The Pallas search hands the crude kernel a (nq, |K_fast|*m) table
    and the refine kernel a (nq, (K - |K_fast|)*m) one, for an
    interleaved fast set; the ``repro.engine.search`` span records both
    widths."""
    from repro.api.serving import AnnEngine
    from repro.core.icq import ICQStructure
    from repro.index import make_index

    n, K, m, d, nq = 512, 8, 16, 16, 4
    rng = np.random.default_rng(0)
    emb = rng.standard_normal((n, d)).astype(np.float32)
    codes = rng.integers(0, m, (n, K)).astype(np.uint8)
    C = rng.standard_normal((K, m, d)).astype(np.float32)
    st = ICQStructure(xi=np.arange(d) < 8,
                      fast_mask=np.isin(np.arange(K), (1, 5)),
                      sigma=np.float32(1.0))
    opts = dict(emb_db=emb, n_lists=8, n_probe=2) if kind == "ivf" else {}
    idx = make_index(kind, codes, C, st, topk=5, backend="pallas", **opts)
    q = rng.standard_normal((nq, d)).astype(np.float32)

    lut_arg = {"two-step": {"crude": 1, "refine": 1},
               "ivf": {"crude": 2, "refine": 1}}[kind]
    widths = {}
    for eqn in _pallas_calls(jax.make_jaxpr(idx.search)(q).jaxpr):
        kernel = eqn.params["jaxpr"].debug_info.func_src_info
        stage = "crude" if "_crude_" in kernel else "refine"
        widths[stage] = eqn.invars[lut_arg[stage]].aval.shape[1]
    assert widths == {"crude": 2 * m, "refine": 6 * m}

    engine = AnnEngine(idx)
    engine.search(q)
    with jax.profiler.trace(str(tmp_path)):
        engine.search(q)
    pd = jax.profiler.ProfileData.from_file(tracing.find_xplane(str(tmp_path)))
    attrs = [dict(e.stats) for plane in pd.planes for line in plane.lines
             for e in line.events if e.name == "repro.engine.search"]
    assert len(attrs) == 1
    assert attrs[0]["crude_cols"] == 2 * m
    assert attrs[0]["refine_cols"] == 6 * m
    assert attrs[0]["backend"] == "pallas"


_SHARDED_SPAN_SCRIPT = """
import json, sys
import jax, numpy as np
from repro.api import AnnEngine
from repro.core.icq import ICQStructure
from repro.index import make_index

n, K, m, d, nq = 1030, 8, 16, 16, 4
rng = np.random.default_rng(0)
st = ICQStructure(xi=np.arange(d) < 8, fast_mask=np.isin(np.arange(K), (1, 5)),
                  sigma=np.float32(1.0))
idx = make_index("two-step", rng.integers(0, m, (n, K)).astype(np.uint8),
                 rng.standard_normal((K, m, d)).astype(np.float32), st,
                 topk=5, backend="pallas")
engine = AnnEngine(idx, mesh=jax.make_mesh((4,), ("data",)))
q = rng.standard_normal((nq, d)).astype(np.float32)
engine.search(q)
with jax.profiler.trace(sys.argv[1]):
    engine.search(q)
pd = jax.profiler.ProfileData.from_file(
    __import__("glob").glob(sys.argv[1] + "/**/*.xplane.pb",
                            recursive=True)[0])
attrs = [dict(e.stats) for p in pd.planes for line in p.lines
         for e in line.events if e.name == "repro.engine.search"]
print("RESULT " + json.dumps(attrs))
"""


def test_a_sharded_search_span_names_its_shards_and_kernels(tmp_path):
    """Over a four-device mesh (virtual CPU devices, in a subprocess:
    this suite sees one) the ``repro.engine.search`` span of a search on
    the fused kernels records the shard count, the backend and each
    kernel's contraction width."""
    import json
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = " ".join(filter(None, [
        env.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=4"]))
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SHARDED_SPAN_SCRIPT,
                           str(tmp_path)], env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    attrs = json.loads(line[len("RESULT "):])
    assert len(attrs) == 1
    assert attrs[0]["shards"] == 4
    assert attrs[0]["backend"] == "pallas"
    assert attrs[0]["crude_cols"] == 2 * 16
    assert attrs[0]["refine_cols"] == 6 * 16
