"""The main path's device programs compile for the chip — without the chip.

The TPU's compiler is installed here and compiles for a DESCRIBED v5e
(``jax.experimental.topologies``), so what it refuses, and what does not
fit the device, fails in tier-1 and costs no chip time.  A compile that
passes is not a chip run (``chip_smoke.py`` is), and says nothing of
results or times.

The program makes the same kernel choices on every backend, so what is
compiled here is what the chip runs.

Sizes.  Kernels whose compile time does not grow with the lane compile
at the TPC-H SF=1 ``lineitem`` bucket, 2^23 rows.  A full sort of an
emulated 64-bit lane (``_sort_kernel``, ``lax.top_k``, the unsorted
unique join) takes minutes to compile there (CHANGES.md, PR 22), so
tier-1 compiles those at 2^10 — enough for the compiler to refuse a
lowering — and the 2^23 cases are marked ``slow``.  The fused devpipe
programs of Q1/Q3/Q6 take their structure from loaded data: they are
built at SF=0.05 (``lineitem`` bucket 2^19), the largest scale that
loads and prepares in a few seconds; their SF=1 compiles were made once
by hand and are in CHANGES.md (PR 22).

Everything that touches the topology lives in fixtures of THIS file: the
worker that is handed the file loads the TPU's library, and no other.
"""
import hashlib
import json
import os
import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from tinysql_tpu.bench import tpch
from tinysql_tpu.ops import kernels, progcache, shardops
from tinysql_tpu.session.session import new_session

HBM_BYTES = 16 * 10 ** 9   # one v5e chip
SF1_ROWS = 1 << 23         # TPC-H SF=1 lineitem bucket
SMALL_ROWS = 1 << 10
DEVPIPE_SF = 0.05


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    kernels.jax()
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *abstract):
    """Lower + compile for the described device; the program must fit
    one chip's memory.  Returns the compiled executable."""
    compiled = fn.lower(*abstract).compile()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes)
    assert used < HBM_BYTES, (used, ma)
    return compiled


def _lane(sharding, dtype, n):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


_ROWS = [SMALL_ROWS, pytest.param(SF1_ROWS, marks=pytest.mark.slow)]


# ---- kernels keyed by the bucket alone ------------------------------------

@pytest.mark.parametrize("rows", _ROWS)
@pytest.mark.parametrize("dtype, desc", [("int64", False),
                                         ("float64", True)])
def test_sort_kernel(one_chip, no_persistent_cache, dtype, desc, rows):
    s = one_chip
    _compile(kernels._sort_kernel((desc,)), [_lane(s, dtype, rows)],
             [_lane(s, "bool", rows)], _lane(s, "bool", rows))


@pytest.mark.parametrize("rows", _ROWS)
def test_topk_kernel(one_chip, no_persistent_cache, rows):
    _compile(kernels._topk_kernel(16), _lane(one_chip, "float64", rows))


@pytest.mark.parametrize("build_sorted, rows", [
    (True, SF1_ROWS), (False, SMALL_ROWS),
    pytest.param(False, SF1_ROWS, marks=pytest.mark.slow)])
def test_unique_join_kernel(one_chip, no_persistent_cache, build_sorted, rows):
    s = one_chip
    probe = [_lane(s, "int64", rows), _lane(s, "bool", rows),
             _lane(s, "bool", rows)]
    build = [_lane(s, "int64", rows // 4), _lane(s, "bool", rows // 4),
             _lane(s, "bool", rows // 4)]
    _compile(kernels._unique_join_kernel(build_sorted), *probe, *build)


def test_segment_aggregate_unrolled(one_chip, no_persistent_cache):
    """Q1's shape on the per-operator tier: a handful of segments, sums
    of expressions over float64 lanes, min/max, presence — unrolled
    masked reductions, the branch only a non-cpu backend takes."""
    jn = kernels.jnp()

    def kernel(gid, valid, price, disc, tax):
        seg = kernels._SegReduce(jax, jn, gid, valid, 16)
        assert seg.unroll
        presence, first = seg.presence_first()
        return (presence, first, seg.sum(price, valid),
                seg.sum(price * (1 - disc), valid),
                seg.sum(price * (1 - disc) * (1 + tax), valid),
                seg.minmax(tax, valid, True))
    s = one_chip
    _compile(jax.jit(kernel), _lane(s, "int64", SF1_ROWS),
             _lane(s, "bool", SF1_ROWS), _lane(s, "float64", SF1_ROWS),
             _lane(s, "float64", SF1_ROWS), _lane(s, "float64", SF1_ROWS))


@pytest.mark.parametrize("dtype", ["float64", "int64"])
def test_prefix_sum(one_chip, no_persistent_cache, dtype):
    """The group-index aggregate's segment sums: ``jnp.cumsum`` of a
    float64 lane crashed this compiler inside the fused Q1 program."""
    _compile(jax.jit(kernels.prefix_sum), _lane(one_chip, dtype, SF1_ROWS))


def test_lex_head(one_chip, no_persistent_cache):
    """Q3's TopN at SF=1: five sort operands over the 2^21 join output,
    a window of 16 — selected, not sorted."""
    s, n = one_chip, 1 << 21
    ops = [_lane(s, "int64", n), _lane(s, "int8", n),
           _lane(s, "float64", n), _lane(s, "int8", n),
           _lane(s, "int8", n)]
    _compile(jax.jit(lambda o: kernels.lex_head(o, 16)), ops)


def test_sharded_topk_on_four_chip_mesh(topo, no_persistent_cache):
    """One ``shard_map`` program of ops/shardops.py on a mesh built from
    the four described devices: per-shard top-k, all_gather, merge."""
    mesh = Mesh(np.array(topo.devices), ("shard",))
    n = len(topo.devices)
    assert n == 4
    score = jax.ShapeDtypeStruct(
        (n * SMALL_ROWS,), "float64",
        sharding=NamedSharding(mesh, P("shard")))
    compiled = _compile(
        shardops._topk_merge_kernel(mesh, n, 16, SMALL_ROWS, "float64"),
        score)
    # the compiler may turn the gather into an all-reduce of a padded lane
    assert re.search(r"all-(gather|reduce)", compiled.as_text())


@pytest.mark.parametrize("dtype", ["int64", "float64"])
def test_mesh_min_max_merge(topo, no_persistent_cache, dtype):
    """The sharded aggregates' partial merge: ``lax.pmin`` of an int64
    was refused by this compiler (UNIMPLEMENTED: only Sum all-reduce of
    an emulated 64-bit type); gather-and-reduce compiles."""
    from tinysql_tpu.parallel import dist
    mesh = Mesh(np.array(topo.devices), ("shard",))

    def body(x):
        local = jax.numpy.min(x, axis=0, keepdims=True)
        return (dist.mesh_min(local), dist.mesh_max(local),
                jax.lax.psum(local, "shard"))
    fn = jax.jit(dist.shard_map_unchecked(
        body, mesh, in_specs=P("shard"), out_specs=P()))
    _compile(fn, jax.ShapeDtypeStruct(
        (len(topo.devices) * SMALL_ROWS,), dtype,
        sharding=NamedSharding(mesh, P("shard"))))


# ---- the fused devpipe programs of Q1, Q3, Q6 -----------------------------

class _Captured(Exception):
    def __init__(self, fn, args):
        super().__init__("captured")
        self.fn, self.args = fn, args


@pytest.fixture(scope="module")
def tpch_session():
    s = new_session()
    tpch.load(s, data=tpch.generate(DEVPIPE_SF))
    # force the fused pipeline (a bail-out then re-raises, it does not
    # fall back) and let Q6's small estimate reach the device tier
    s.execute("set @@tidb_devpipe = 1")
    s.execute("set @@tidb_tpu_min_rows = 0")
    return s


def _capture(monkeypatch, session, sql, one_chip):
    """(the statement's first device program, its inputs as shapes on
    the described chip): the statement runs up to its first dispatch."""
    def capturing_jit(fn, name="", **kw):
        def call(*args):
            raise _Captured(jax.jit(fn, **kw), args)
        return call
    monkeypatch.setattr(kernels, "counted_jit", capturing_jit)
    try:
        with pytest.raises(_Captured) as got:
            session.query(sql)
    finally:
        progcache.clear()  # the registry now holds the capturing stand-in
    return got.value.fn, jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        got.value.args)


@pytest.mark.parametrize("name", ["Q1", "Q3", "Q6"])
def test_fused_devpipe_program(one_chip, no_persistent_cache, monkeypatch,
                               tpch_session, name):
    """Run the statement up to its first dispatch, capture the fused
    program with its inputs, and compile THAT for the described device
    instead of running it here."""
    fn, abstract = _capture(monkeypatch, tpch_session, tpch.QUERIES[name],
                            one_chip)
    _compile(fn, *abstract)


# ---- the same three over the four-chip mesh --------------------------------

def _compile_mesh_statement(topo, monkeypatch, session, sql):
    """The one fused program of :func:`_capture_mesh` — row-sharded
    lanes, per-shard partial states, their merge — compiles for the
    v5e:2x2 with its collectives in it.  Returns the compiled program's
    text."""
    fn, abstract = _capture_mesh(topo, monkeypatch, session, sql)
    return _compile(fn, *abstract).as_text()


def _capture_mesh(topo, monkeypatch, session, sql):
    """Under ``tidb_mesh_parallel = 1`` with the session's mesh built from
    the four described devices: the statement runs up to its first
    dispatch, its lanes "placed" as shapes with their layouts (nothing
    can be put on a described device).  Returns (the program, its inputs
    as shapes with their layouts)."""
    from tinysql_tpu.parallel import dist
    mesh = Mesh(np.array(topo.devices), ("shard",))
    monkeypatch.setattr(dist, "make_mesh", lambda n=None: mesh)
    monkeypatch.setattr(dist, "_SESSION_MESH", None)
    # a mesh file that ran before on this worker left its CPU submeshes
    monkeypatch.setattr(dist, "_SIZED_MESHES", {})
    monkeypatch.setattr(dist, "MIN_SHARD_ROWS", 16)  # Q6's small estimate
    monkeypatch.setattr(
        dist, "place", lambda host, layout: jax.ShapeDtypeStruct(
            host.shape, host.dtype, sharding=layout))
    upload = kernels.h2d
    monkeypatch.setattr(
        kernels, "h2d", lambda a, layout=None: upload(a) if layout is None
        else jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                  sharding=layout))

    def capturing_jit(fn, name="", **kw):
        def call(*args):
            raise _Captured(jax.jit(fn, **kw), args)
        return call
    monkeypatch.setattr(kernels, "counted_jit", capturing_jit)
    monkeypatch.setattr(kernels, "_stackable_jit",
                        lambda fn, *a, **kw: capturing_jit(fn))
    session.execute("set @@tidb_mesh_parallel = 1")
    session.execute("set @@tidb_tpu_min_rows = 64")
    try:
        with pytest.raises(_Captured) as got:
            session.query(sql)
    finally:
        session.execute("set @@tidb_mesh_parallel = 0")
        session.execute("set @@tidb_tpu_min_rows = 0")
        progcache.clear()  # the registry now holds the capturing stand-in
    rows, whole = dist.rows(mesh), dist.whole(mesh)
    leaves = jax.tree_util.tree_leaves(got.value.args)
    placed = [x for x in leaves if isinstance(x, jax.ShapeDtypeStruct)]
    assert any(x.sharding == rows for x in placed)
    return got.value.fn, jax.tree_util.tree_map(
        lambda x: x if isinstance(x, jax.ShapeDtypeStruct)
        else jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                  sharding=whole), got.value.args)


@pytest.mark.parametrize("name", ["Q1", "Q3", "Q6"])
def test_fused_mesh_program(topo, no_persistent_cache, monkeypatch,
                            tpch_session, name):
    text = _compile_mesh_statement(topo, monkeypatch, tpch_session,
                                   tpch.QUERIES[name])
    assert re.search(r"all-(gather|reduce)", text)


# ---- column liveness: what the compiled text holds --------------------------

def _all_live(monkeypatch):
    """Every fused program computes every slot of its root, whatever the
    operator above it reads: the program of the commit before liveness."""
    from tinysql_tpu.executor import devpipe
    run = devpipe.DevPipeExec._run_pipeline

    def run_all(self):
        self.live = None
        return run(self)
    monkeypatch.setattr(devpipe.DevPipeExec, "_run_pipeline", run_all)


def _gathers(text, rows, dtype=r"\w+"):
    """The compiled text's gathers whose result is a lane of ``rows``
    elements (of ``dtype``: any), counted by the pipeline node that
    traced them (the scopes of ``_TView`` in the op_name: ``aggindex``,
    ``join/join`` for the inner join, ``join`` for the outer)."""
    import collections
    count = collections.Counter()
    for line in text.splitlines():
        m = re.search(rf"= {dtype}\[(\d+)\]\S* gather\(", line)
        if not m or int(m.group(1)) != rows:
            continue
        scopes = re.search(r'op_name="([^"]*)"', line).group(1).split("/")
        node = "aggindex" if "aggindex" in scopes else \
            "/".join(x for x in scopes if x == "join")
        count[node] += 1
    return dict(count)


#: Q3 at SF=0.05: ``orders`` and the group table share the 2^17 bucket
Q3_BUCKET = 1 << 17


def _no_alignment(monkeypatch):
    """No join reads its build's rows in place: every one looks them up
    by its key and gathers them, the programs of the commit before
    PR 40."""
    from tinysql_tpu.executor import devpipe
    monkeypatch.setattr(devpipe._JoinNode, "_aligned",
                        lambda self, ptv, btv: False)


def _no_fold(monkeypatch):
    """No join folds its build's validity into its key -> row table:
    every one reads the validity at each of its probe's rows, as the
    programs did before the fold."""
    from tinysql_tpu.executor import devpipe
    monkeypatch.setattr(devpipe._JoinNode, "_folded",
                        lambda self, tbl_len, nb, mesh: False)


def _no_count32(monkeypatch):
    """No sorted GROUP BY counts its rows in 32 bits: every count is an
    int64, two words a boundary, as the programs did before."""
    from tinysql_tpu.executor import devpipe
    monkeypatch.setattr(devpipe._AggIndexNode, "_count32",
                        lambda self, nb: False)


#: ``customer``'s key -> row table at SF=0.05 (``c_custkey`` 1 to 7,500)
CUSTOMER_TABLE = 7500


@pytest.mark.parametrize("count32", ["on", "off"])
@pytest.mark.parametrize("fold", ["on", "off"])
@pytest.mark.parametrize("aligned", ["on", "off"])
@pytest.mark.parametrize("live, expect", [
    # the benchmark's statement: the projection above the TopN reads
    # revenue, l_orderkey, o_orderdate, o_shippriority of eight slots;
    # the inner join keeps tbl[pos0] and bvalid[pos] and gathers nothing
    # of customer's two columns (four u32 and two pred gathers gone).
    # The aggregate's boundary gathers are presence's and revenue's:
    # l_extendedprice * (1 - l_discount) is NULL on no row, so its count
    # is presence (the parent held six: a count's two more).  The outer
    # join reads revenue and l_orderkey off the group table, whose group
    # g is orders' row g (both stored by the order key, every order with
    # lines): aligned, it reads them in place and gathers nothing; the
    # gathering join (PR 39) takes tbl[pos0], bvalid[pos] and two u32 a
    # value, no null lane (the view proves both free of NULLs in a group
    # that exists; PR 35: 8); with every slot live the inner join reads
    # customer's two columns the same way (PR 35: 8)
    ("consumer", {"aggindex": 4, "join/join": 2}),
    ("all", {"aggindex": 4, "join/join": 6}),
])
def test_q3_gathers_at_the_bucket(one_chip, no_persistent_cache, monkeypatch,
                                  tpch_session, live, expect, aligned, fold,
                                  count32):
    """The inner join's key -> row table (``customer``'s 7,500 keys)
    holds under half of ``orders``' 2^17 rows: the join folds the
    view's validity into it, and its ``bvalid[pos]`` at the bucket
    becomes one ``pred`` gather of the table's length (``off``: as
    before the fold).  The outer join's group table (75,000 keys) holds
    more than half: its gathering form (``aligned`` off) does not fold.
    ``presence`` is counted in 32 bits (``count32``), one boundary
    gather where its int64 takes two (``off``)."""
    if live == "all":
        _all_live(monkeypatch)
    if aligned == "off":
        _no_alignment(monkeypatch)
        expect = dict(expect, join=6)
    if count32 == "off":
        _no_count32(monkeypatch)
    else:
        expect = dict(expect, aggindex=expect["aggindex"] - 1)
    if fold == "off":
        _no_fold(monkeypatch)
    else:
        expect = dict(expect, **{"join/join": expect["join/join"] - 1})
    fn, abstract = _capture(monkeypatch, tpch_session, tpch.QUERIES["Q3"],
                            one_chip)
    text = _compile(fn, *abstract).as_text()
    assert _gathers(text, Q3_BUCKET) == expect
    assert _gathers(text, CUSTOMER_TABLE, "pred") == \
        ({"join/join": 1} if fold == "on" else {})


#: a chip's quarter of ``lineitem``'s bucket holds a quarter of the
#: orders give or take the data's draw; its span of groups pads to five
#: sixteenths of the group table (``devpipe._span_pad``)
Q3_SPAN_PAD = 5 * Q3_BUCKET // 16


def _collectives(text, kind, rows):
    """The compiled text's ``kind`` collectives whose result is, or
    holds, a lane of ``rows`` elements (an all-gather's: every chip's
    part)."""
    out = []
    for line in text.splitlines():
        head, found, _ = line.partition(f" {kind}(")
        if found and f"[{rows}]" in head.partition(" = ")[2]:
            out.append(line)
    return out


@pytest.mark.parametrize("count32", ["on", "off"])
@pytest.mark.parametrize("fold", ["on", "off"])
@pytest.mark.parametrize("aligned", ["on", "off"])
@pytest.mark.parametrize("live, quarter", [
    ("consumer", {"join/join": 2}),
    ("all", {"join/join": 6}),
])
def test_q3_mesh_gathers_a_chip(topo, no_persistent_cache, monkeypatch,
                                tpch_session, live, quarter, aligned, fold,
                                count32):
    """Under the mesh every chip bounds its own span of the groups (three
    gathers at the span's pad, none at the whole table's bucket), the
    pieces cross as all-gathers and no whole table is summed over the
    chips; and it joins its quarter of ``orders``: the outer join reads
    its quarter of the merged group table in place, where the gathering
    join (``off``) takes six gathers of a quarter lane.  ``presence``'s
    piece is one 32-bit lane (``count32``), where its int64 is two."""
    if live == "all":
        _all_live(monkeypatch)
    if aligned == "off":
        _no_alignment(monkeypatch)
        quarter = dict(quarter, join=6)
    # a chip's 2^15 rows of orders against customer's 7,500 keys: the
    # inner join folds, each chip its own copy of the whole table
    if fold == "off":
        _no_fold(monkeypatch)
    else:
        quarter = dict(quarter, **{"join/join": quarter["join/join"] - 1})
    if count32 == "off":
        _no_count32(monkeypatch)
    words = 3 if count32 == "on" else 4
    text = _compile_mesh_statement(topo, monkeypatch, tpch_session,
                                   tpch.QUERIES["Q3"])
    assert _gathers(text, Q3_BUCKET) == {}
    assert _gathers(text, Q3_SPAN_PAD) == {"aggindex": words}
    assert _gathers(text, Q3_BUCKET // 4) == quarter
    assert _gathers(text, CUSTOMER_TABLE, "pred") == \
        ({"join/join": 1} if fold == "on" else {})
    # revenue's two 32-bit halves and presence's one (its int64's two),
    # a piece a chip
    assert len(_collectives(text, "all-gather", 4 * Q3_SPAN_PAD)) == words
    assert not _collectives(text, "all-gather", 4 * Q3_BUCKET)
    assert not _collectives(text, "all-reduce", Q3_BUCKET)


def _no_flags(monkeypatch):
    """Every view is built with no slot proved free of NULLs: the
    programs of the commit before the flags."""
    from tinysql_tpu.executor import devpipe
    init = devpipe._TView.__init__

    def init_empty(self, emit, nb, meta, scope, nonnull=frozenset()):
        init(self, emit, nb, meta, scope)
    monkeypatch.setattr(devpipe._TView, "__init__", init_empty)


def _pinned(name):
    with open(os.path.join(os.path.dirname(__file__), "testdata",
                           name)) as f:
        pinned = json.load(f)
    if pinned["jax"] != jax.__version__:
        pytest.skip(f"pinned under jax {pinned['jax']}")
    return pinned["sha256"]


def _lowered(topo, one_chip, monkeypatch, session, sql, where):
    """The sha256 of the StableHLO ``sql``'s fused program lowers to, on
    one described chip or over the v5e:2x2."""
    fn, abstract = _capture(monkeypatch, session, sql, one_chip) \
        if where == "one" \
        else _capture_mesh(topo, monkeypatch, session, sql)
    return hashlib.sha256(fn.lower(*abstract).as_text().encode()) \
        .hexdigest()


@pytest.mark.parametrize("flags", ["off", "on"])
@pytest.mark.parametrize("where", ["one", "mesh"])
@pytest.mark.parametrize("name", ["Q1", "Q3", "Q6"])
def test_lowered_text_is_the_parents(topo, one_chip, no_persistent_cache,
                                     monkeypatch, tpch_session, name, where,
                                     flags):
    """With no view saying which of its slots hold no NULL (``off``),
    the programs of Q1, Q3 and Q6 lower to the text pinned at 129247b,
    which PR 35 left as it was, byte for byte, on one chip and on the
    mesh: a join gathers every null lane and an aggregate reduces every
    count, as before.  With the flags, Q1 (a dense GROUP BY at the
    statement's root: no reader of a flag) and Q6 (no fused pipeline)
    still do, and Q3, whose outer join gathers two null lanes fewer,
    lowers to the text pinned with PR 36.  PR 38 left those ten as they
    were and pinned Q3's mesh program anew, with the flags and without:
    a chip bounds its own span of the groups, and the pieces are
    gathered and added into place where whole tables were summed.  With
    no join aligned (the gathering join's path) all twelve still do.
    So they do with no join folded and no count in 32 bits."""
    if flags == "off":
        _no_flags(monkeypatch)
    _no_alignment(monkeypatch)
    _no_fold(monkeypatch)
    _no_count32(monkeypatch)
    case = f"{name}.{where}"
    if case == "Q3.mesh":
        pinned = _pinned("lowered_at_pr38.json")[f"{case}.{flags}"]
    else:
        pinned = _pinned("lowered_at_pr36.json" if (name, flags)
                         == ("Q3", "on") else "lowered_at_129247b.json")[case]
    assert _lowered(topo, one_chip, monkeypatch, tpch_session,
                    tpch.QUERIES[name], where) == pinned


@pytest.mark.parametrize("flags", ["off", "on"])
@pytest.mark.parametrize("name, where", [
    ("Q3", "one"), ("Q3", "mesh"), ("Q10", "one"), ("Q18", "one"),
    ("Q5", "one"), ("Q1", "one"), ("Q6", "mesh")])
def test_aligned_programs_lower_to_the_text_pinned_at_pr40(
        topo, one_chip, no_persistent_cache, monkeypatch, tpch_session, name,
        where, flags):
    """With the aligned joins (PR 40) Q3's, Q10's and Q18's programs
    lower to the text pinned with PR 40, with the NULL-freedom flags and
    without; Q5, whose joins are many-to-one or into another table's
    order, and Q1 and Q6, which have none, to their parents' text.
    That holds with no join folded and no count in 32 bits."""
    if flags == "off":
        _no_flags(monkeypatch)
    _no_fold(monkeypatch)
    _no_count32(monkeypatch)
    case = f"{name}.{where}.{flags}"
    if name in ("Q3", "Q10", "Q18"):
        pinned = _pinned("lowered_at_pr40.json")[case]
    elif name == "Q5":
        pinned = _pinned("lowered_at_pr38_joins.json")[case]
    else:
        pinned = _pinned("lowered_at_129247b.json")[f"{name}.{where}"]
    sql = tpch.ALL_QUERIES[name]
    assert _lowered(topo, one_chip, monkeypatch, tpch_session, sql,
                    where) == pinned


@pytest.mark.parametrize("flags", ["off", "on"])
@pytest.mark.parametrize("name, where", [
    ("Q3", "one"), ("Q3", "mesh"), ("Q5", "one"), ("Q5", "mesh"),
    ("Q10", "one"), ("Q18", "one"), ("Q1", "one"), ("Q6", "mesh")])
def test_folded_programs_lower_to_the_pinned_text(
        topo, one_chip, no_persistent_cache, monkeypatch, request,
        tpch_session, name, where, flags):
    """With the joins whose key -> row table holds at most half the
    probe's rows on a device folding the build's validity into it, the
    programs of Q3, Q5, Q10 and Q18 lower to the text pinned in
    ``lowered_folded.json``, with the NULL-freedom flags and without
    (over the mesh as the four-chip cells price it); Q1 and Q6, which
    join nothing, to their parents' text.  That holds with no count in
    32 bits."""
    if where == "mesh":
        request.getfixturevalue("four_devices")
    if flags == "off":
        _no_flags(monkeypatch)
    _no_count32(monkeypatch)
    if name in ("Q1", "Q6"):
        pinned = _pinned("lowered_at_129247b.json")[f"{name}.{where}"]
    else:
        pinned = _pinned("lowered_folded.json")[f"{name}.{where}.{flags}"]
    assert _lowered(topo, one_chip, monkeypatch, tpch_session,
                    tpch.ALL_QUERIES[name], where) == pinned


@pytest.mark.parametrize("flags", ["off", "on"])
@pytest.mark.parametrize("name, where", [
    ("Q3", "one"), ("Q3", "mesh"), ("Q10", "one"), ("Q18", "one"),
    ("Q5", "one"), ("Q5", "mesh"), ("Q1", "one"), ("Q6", "mesh")])
def test_count32_programs_lower_to_the_pinned_text(
        topo, one_chip, no_persistent_cache, monkeypatch, request,
        tpch_session, name, where, flags):
    """With the sorted GROUP BYs counting their rows in 32 bits, the
    programs of Q3, Q10 and Q18 lower to the text pinned in
    ``lowered_count32.json``, with the NULL-freedom flags and without;
    Q5 (its GROUP BY is keyed), Q1 (a dense one) and Q6 (a scalar
    aggregate), which have no sorted GROUP BY, to their parents'
    text."""
    if where == "mesh":
        request.getfixturevalue("four_devices")
    if flags == "off":
        _no_flags(monkeypatch)
    if name in ("Q1", "Q6"):
        pinned = _pinned("lowered_at_129247b.json")[f"{name}.{where}"]
    elif name == "Q5":
        pinned = _pinned("lowered_folded.json")[f"{name}.{where}.{flags}"]
    else:
        pinned = _pinned("lowered_count32.json")[f"{name}.{where}.{flags}"]
    assert _lowered(topo, one_chip, monkeypatch, tpch_session,
                    tpch.ALL_QUERIES[name], where) == pinned


# ---- the join chains: Q5, Q10, Q18 as one fused program each ---------------

#: seconds to capture and compile one of the three for the described v5e
#: at the SF=0.05 shapes (read here: 4 to 6 s each; a 64-bit sort of a
#: bucket-wide lane alone takes minutes)
JOIN_COMPILE_BUDGET_S = 60
#: ``orders`` at SF=0.05 (75 k rows); ``lineitem``'s bucket is 2^19
ORDERS_BUCKET = 1 << 17


def _sorted_lanes(text):
    """The lane lengths of every sort in a compiled program's text."""
    out = []
    for line in text.splitlines():
        head, found, _ = line.partition(" sort(")
        if found:
            out += [int(n) for n in re.findall(r"\[(\d+)\]", head)]
    return out


@pytest.mark.parametrize("name", ["Q5", "Q10", "Q18"])
def test_fused_join_chain_program(one_chip, no_persistent_cache, monkeypatch,
                                  tpch_session, name):
    """Each of the three is ONE program (the capture raises at the first
    dispatch: a statement that fell to the per-operator tier would show
    a join's or an aggregate's kernel here, not a pipe), compiles inside
    the budget, fits the chip, and sorts no lane of the scan's or the
    orders' bucket: the GROUP BY above the chain is keyed, Q18's TopN
    head of 128 is selected."""
    import time
    from tinysql_tpu.executor import devpipe
    t0 = time.time()
    progcache.clear()
    monkeypatch.setattr(devpipe, "COMPILED_NODE_KEYS", set())
    fn, abstract = _capture(monkeypatch, tpch_session, tpch.WORKLOAD[name],
                            one_chip)
    text = _compile(fn, *abstract).as_text()
    assert time.time() - t0 < JOIN_COMPILE_BUDGET_S
    kinds = {k[0] for k in devpipe.COMPILED_NODE_KEYS}
    assert "join" in kinds and "order" in kinds, kinds
    assert "sortgroup" not in kinds, kinds
    assert all(n < ORDERS_BUCKET for n in _sorted_lanes(text)), \
        sorted(set(_sorted_lanes(text)))


def _scatters(text, scope):
    """The compiled text's scatters traced under a node of ``scope``."""
    return sum(1 for line in text.splitlines() if " scatter(" in line
               and scope in re.search(r'op_name="([^"]*)"',
                                      line).group(1).split("/"))


#: ``lineitem``'s bucket at SF=0.05
LINEITEM_BUCKET = 1 << 19


#: (the joins whose ``bvalid[pos]`` at the probe's bucket the fold takes
#: away, the fold's ``pred`` gathers by the table's length) at SF=0.05:
#: Q5's into supplier (500 keys) and into (orders join customer) (75,000
#: order keys) at lineitem's 2^19 rows, and orders' into customer (7,500
#: keys) at 2^17; Q10's and Q18's into customer
FOLDED = {
    "Q5": ({"join/join": 1, "join/join/join": 1},
           {500: {"join/join": 1}, 75000: {"join/join/join": 1},
            CUSTOMER_TABLE: {"join/join/join/join": 1}}),
    "Q10": ({"join/join/join": 1},
            {CUSTOMER_TABLE: {"join/join/join": 1}}),
    "Q18": ({"join/join": 1}, {CUSTOMER_TABLE: {"join/join": 1}}),
}


@pytest.mark.parametrize("count32", ["on", "off"])
@pytest.mark.parametrize("fold", ["on", "off"])
@pytest.mark.parametrize("aligned", ["on", "off"])
@pytest.mark.parametrize("name, rows, gathers, scatters, gathering", [
    # the two joins at the lineitem bucket gather (orders join customer)'s
    # c_nationkey and supplier's s_nationkey: tbl[pos0], bvalid[pos] and
    # two u32 of the value each, and no null lane (the parent: 5 and 5);
    # both are many-to-one: nothing is aligned
    ("Q5", LINEITEM_BUCKET, {"join/join": 4, "join/join/join": 4}, 0, {}),
    # at the orders bucket: the aggregate's four boundary gathers with
    # its counts in 64 bits (three in 32: ``count32``); the
    # join into the group table reads revenue in place (the gathering
    # join: tbl[pos0], bvalid[pos], two u32), the join below it
    # c_custkey's side, each without its null lane (the parent: 5 and
    # 5); the keyed GROUP BY on c_custkey scatter-adds presence and the
    # sum, whose own count is presence (the parent: 3 scatters)
    ("Q10", ORDERS_BUCKET, {"aggindex": 4, "join/join/join": 4}, 2,
     {"join/join": 4}),
    # two aggregates of four boundary gathers (three each in 32 bits);
    # sum(l_quantity) off the
    # group table in place (the gathering join: 4), and two columns
    # under the join below, whose semi join on the HAVING view reads
    # that view's validity in place (its two gathers count there),
    # without null lanes (the parent: 5, 10)
    ("Q18", ORDERS_BUCKET, {"aggindex": 8, "join/join": 6}, 0,
     {"join": 4, "join/join": 2}),
])
def test_join_chains_gather_no_constant_null_lane(
        one_chip, no_persistent_cache, monkeypatch, tpch_session, name, rows,
        gathers, scatters, gathering, aligned, fold, count32):
    """The chains' gathers at the probes' buckets and the keyed GROUP
    BY's scatters, counted by the node that traced them: a build column
    whose view holds no NULL on a valid row (``_TView.nonnull``) is
    gathered without its null lane, and a sum of such an argument
    reduces no count of its own.  A join into a view whose rows are
    the probe's own (``aligned``) gathers nothing; ``off``: the
    gathering join's (``gathering`` more).  A join whose key -> row
    table holds at most half its probe's rows reads the build's
    validity once a key (``FOLDED``); ``fold`` off: once a probe row.
    A sorted GROUP BY counts its rows in 32 bits (``count32``): one
    boundary gather of ``presence`` where its int64 takes two."""
    if aligned == "off":
        _no_alignment(monkeypatch)
        gathers = {k: gathers.get(k, 0) + gathering.get(k, 0)
                   for k in gathers.keys() | gathering.keys()}
    gone, tables = FOLDED[name]
    if fold == "off":
        _no_fold(monkeypatch)
    else:
        gathers = {k: n - gone.get(k, 0) for k, n in gathers.items()}
    if count32 == "off":
        _no_count32(monkeypatch)
    elif "aggindex" in gathers:
        # four boundary gathers an aggregate, one of them a count's
        gathers = dict(gathers, aggindex=gathers["aggindex"] * 3 // 4)
    fn, abstract = _capture(monkeypatch, tpch_session, tpch.WORKLOAD[name],
                            one_chip)
    text = _compile(fn, *abstract).as_text()
    assert _gathers(text, rows) == gathers
    assert _scatters(text, "keygroup") == scatters
    for length, by_node in tables.items():
        assert _gathers(text, length, "pred") == \
            (by_node if fold == "on" else {}), length


@pytest.mark.parametrize("flags", ["off", "on"])
@pytest.mark.parametrize("name", ["Q5", "Q10", "Q18"])
def test_join_chains_lower_to_the_parents_text_on_one_chip(
        one_chip, no_persistent_cache, monkeypatch, tpch_session, name, flags):
    """PR 39 taught the join's view build and the keyed GROUP BY the
    mesh; on one device the three programs lower to the text pinned at
    f6b51c3 (PR 38), byte for byte, with the NULL-freedom flags and
    without: the one-chip joins cell runs the programs it ran.  So they
    do with no join aligned (the gathering join's path), none folded
    and no count in 32 bits."""
    if flags == "off":
        _no_flags(monkeypatch)
    _no_alignment(monkeypatch)
    _no_fold(monkeypatch)
    _no_count32(monkeypatch)
    fn, abstract = _capture(monkeypatch, tpch_session, tpch.WORKLOAD[name],
                            one_chip)
    text = fn.lower(*abstract).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _pinned("lowered_at_pr38_joins.json")[f"{name}.one.{flags}"]


# ---- the three join chains over the four-chip mesh -------------------------

def _all_gathers(text, scope):
    """The lane lengths of the compiled text's all-gathers traced under
    a node of ``scope`` and the ``mesh.all_gather`` scope."""
    out = []
    for line in text.splitlines():
        head, found, _ = line.partition(" all-gather(")
        name = re.search(r'op_name="([^"]*)"', line)
        if found and name and "mesh.all_gather" in name.group(1) \
                and scope in name.group(1).split("/"):
            out += [int(n) for n in re.findall(r"\[(\d+)\]",
                                               head.partition(" = ")[2])]
    return out


@pytest.mark.parametrize("name, expect", [
    # (orders join customer) is computed a quarter of orders' bucket a
    # chip and brought whole under lineitem's join: its validity and
    # c_nationkey's two 32-bit halves (no null lane: the view proves it
    # free of NULLs); the 25 nations' masked reductions a chip, merged
    # by all-reduce
    ("Q5", {"view_lanes": 3, "keygroup_reduce": True}),
    # the view builds are aggregates, whole as they merge; customer's
    # 2^13 slots (7,500 keys and the NULL's) scatter-added a chip
    ("Q10", {"view_lanes": 0, "keygroup_reduce": True}),
    # aggregates below the joins: nothing keyed above them
    ("Q18", {"view_lanes": 0, "keygroup_reduce": False}),
])
def test_fused_join_chain_mesh_program(topo, no_persistent_cache, monkeypatch,
                                       four_devices, tpch_session, name,
                                       expect):
    """Under ``tidb_mesh_parallel`` each of the three is ONE program
    over the described v5e:2x2 (the capture raises at the first
    dispatch: a statement that left the fused pipeline would show a
    join's or an aggregate's kernel, or a sort-group node, here; the
    planner prices four copies of a broadcast: ``four_devices``),
    compiles inside the budget and sorts no lane of a chip's share of
    the scan's or the orders' bucket: no join partitions, the GROUP BY
    above a chain is keyed and reduced a chip at a time, the TopN heads
    are selected."""
    import time
    from tinysql_tpu.executor import devpipe
    t0 = time.time()
    progcache.clear()
    monkeypatch.setattr(devpipe, "COMPILED_NODE_KEYS", set())
    text = _compile_mesh_statement(topo, monkeypatch, tpch_session,
                                   tpch.WORKLOAD[name])
    assert time.time() - t0 < JOIN_COMPILE_BUDGET_S
    kinds = {k[0] for k in devpipe.COMPILED_NODE_KEYS}
    assert "join" in kinds and kinds & {"order", "order_mesh"}, kinds
    assert not kinds & {"sortgroup", "joinshuf"}, kinds
    assert all(n < ORDERS_BUCKET // 4 for n in _sorted_lanes(text)), \
        sorted(set(_sorted_lanes(text)))
    assert "all-to-all" not in text
    lanes = _all_gathers(text, "join")
    assert len([n for n in lanes if n == ORDERS_BUCKET]) \
        == expect["view_lanes"], lanes
    reduced = any("keygroup" in line and "mesh.psum" in line
                  for line in text.splitlines() if " all-reduce(" in line)
    assert reduced == expect["keygroup_reduce"]


@pytest.mark.parametrize("fold", ["on", "off"])
def test_q5_over_the_mesh_folds_by_a_chips_rows(
        topo, no_persistent_cache, monkeypatch, four_devices, tpch_session,
        fold):
    """Over four chips each probes with its 2^17 of ``lineitem``'s rows.
    ``supplier``'s 500 keys fold; the table of (orders join customer),
    75,000 order keys, holds more than half a chip's rows and keeps its
    ``bvalid[pos]`` at them, as at SF=10, where 15 M keys meet 2^24 rows
    a chip.  Each chip folds its own copy of a whole table: nothing more
    crosses the mesh."""
    if fold == "off":
        _no_fold(monkeypatch)
    text = _compile_mesh_statement(topo, monkeypatch, tpch_session,
                                   tpch.WORKLOAD["Q5"])
    on = fold == "on"
    a_chip = LINEITEM_BUCKET // 4
    assert _gathers(text, a_chip, "pred") == \
        ({"join/join/join": 1} if on else {"join/join": 1,
                                           "join/join/join": 1})
    assert _gathers(text, 500, "pred") == ({"join/join": 1} if on else {})
    assert _gathers(text, 75000, "pred") == {}
    assert _gathers(text, CUSTOMER_TABLE, "pred") == \
        ({"join/join/join/join": 1} if on else {})
    assert len([n for n in _all_gathers(text, "join")
                if n == ORDERS_BUCKET]) == 3


# ---- the same at the SF=10 shapes ------------------------------------------

@pytest.fixture(scope="module")
def sf10_session():
    """The columns Q1/Q3/Q6 and Q5/Q10/Q18 read at TPC-H SF=10 (60 M
    ``lineitem`` rows: about 8 GB of host arrays and 13 GB at the peak,
    minutes of host preparation), for the SF=10 shapes of
    ``tpch_sf10_mesh4`` and ``tpch_sf10_joins_mesh4``."""
    sf = 10.0
    r = np.random.default_rng(7)
    n_cust, n_ord = int(150_000 * sf), int(1_500_000 * sf)
    n_supp = int(10_000 * sf)
    texts = np.array([f"text {i:05d}" for i in range(16384)])
    per = r.integers(1, 8, n_ord)
    n_li = int(per.sum())
    o_days = r.integers(0, 2405, n_ord)
    l_days = np.repeat(o_days, per) + r.integers(1, 122, n_li)
    days = (np.datetime64("1992-01-01") + np.arange(2405 + 122)
            .astype("timedelta64[D]")).astype("<U10")
    okey = np.arange(1, n_ord + 1, dtype=np.int64)
    tables = {
        "region": ("r_regionkey bigint primary key, r_name char(25)", {
            "r_regionkey": np.arange(5, dtype=np.int64),
            "r_name": np.array(tpch._REGIONS)}),
        "nation": ("n_nationkey bigint primary key, n_name char(25), "
                   "n_regionkey bigint", {
                       "n_nationkey": np.arange(25, dtype=np.int64),
                       "n_name": np.array([n for n, _ in tpch._NATIONS]),
                       "n_regionkey": np.array(
                           [g for _, g in tpch._NATIONS], dtype=np.int64)}),
        "supplier": ("s_suppkey bigint primary key, s_nationkey bigint", {
            "s_suppkey": np.arange(1, n_supp + 1, dtype=np.int64),
            "s_nationkey": r.integers(0, 25, n_supp)}),
        "customer": (
            "c_custkey bigint primary key, c_name varchar(25), "
            "c_address varchar(40), c_nationkey bigint, c_phone char(15), "
            "c_acctbal double, c_mktsegment char(10), "
            "c_comment varchar(117)", {
                "c_custkey": np.arange(1, n_cust + 1, dtype=np.int64),
                "c_name": texts[r.integers(0, len(texts), n_cust)],
                "c_address": texts[r.integers(0, len(texts), n_cust)],
                "c_nationkey": r.integers(0, 25, n_cust),
                "c_phone": texts[r.integers(0, len(texts), n_cust)],
                "c_acctbal": np.round(r.uniform(-999.99, 9999.99, n_cust), 2),
                "c_mktsegment": np.array(
                    ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY",
                     "HOUSEHOLD"])[r.integers(0, 5, n_cust)],
                "c_comment": texts[r.integers(0, len(texts), n_cust)]}),
        "orders": ("o_orderkey bigint primary key, o_custkey bigint, "
                   "o_totalprice double, o_orderdate varchar(10), "
                   "o_shippriority int", {
                       "o_orderkey": okey,
                       "o_custkey": r.integers(1, n_cust + 1, n_ord),
                       "o_totalprice": np.round(
                           r.uniform(900.0, 500000.0, n_ord), 2),
                       "o_orderdate": days[o_days],
                       "o_shippriority": np.zeros(n_ord, dtype=np.int64)}),
        "lineitem": (
            "l_id bigint primary key, l_orderkey bigint, l_suppkey bigint, "
            "l_quantity double, "
            "l_extendedprice double, l_discount double, l_tax double, "
            "l_returnflag char(1), l_linestatus char(1), "
            "l_shipdate varchar(10)", {
                "l_id": np.arange(1, n_li + 1, dtype=np.int64),
                "l_orderkey": np.repeat(okey, per),
                "l_suppkey": r.integers(1, n_supp + 1, n_li),
                "l_quantity": r.integers(1, 51, n_li).astype(np.float64),
                "l_extendedprice": np.round(
                    r.uniform(900.0, 105000.0, n_li), 2),
                "l_discount": np.round(r.integers(0, 11, n_li) * 0.01, 2),
                "l_tax": np.round(r.integers(0, 9, n_li) * 0.01, 2),
                "l_returnflag": np.array(["A", "N", "R"])[
                    r.integers(0, 3, n_li)],
                "l_linestatus": np.array(["O", "F"])[r.integers(0, 2, n_li)],
                "l_shipdate": days[l_days]})}
    from tinysql_tpu.columnar.store import bulk_load
    s = new_session()
    s.execute("create database tpch10")
    s.execute("use tpch10")
    for table, (columns, data) in tables.items():
        s.execute(f"create table {table} ({columns})")
        bulk_load(s.storage, s.infoschema().table_by_name("tpch10", table),
                  data)
    s.execute("set @@tidb_devpipe = 1")
    return s


@pytest.mark.slow
@pytest.mark.parametrize("name", ["Q1", "Q3", "Q6"])
def test_fused_mesh_program_at_the_sf10_shapes(topo, no_persistent_cache,
                                               monkeypatch, sf10_session,
                                               name):
    """The three programs of ``tpch_sf10_mesh4.power_stream`` (lanes of
    2^26 and 2^24 rows, 2^24 groups) fit a chip and compile in seconds
    (PERF.md section 5 has them: 5.1, 10.5, 1.5 s), every join a
    broadcast; slow for the minutes of host preparation, not the
    compiles."""
    from tinysql_tpu.parallel import dist
    # the budget as a chip reports its memory, not the CPU's fixed one
    monkeypatch.setattr(dist, "broadcast_budget_bytes",
                        lambda: HBM_BYTES * dist.BROADCAST_MEMORY_SHARE)
    text = _compile_mesh_statement(topo, monkeypatch, sf10_session,
                                   tpch.QUERIES[name])
    assert "all-to-all" not in text
    if name == "Q3":
        # as test_q3_mesh_gathers_a_chip, at 2^24 groups: a chip bounds
        # its span (2^22 groups give or take 1,024: five sixteenths),
        # presence in one 32-bit word
        groups, pad = 1 << 24, 5 << 20
        assert _gathers(text, groups) == {}
        assert _gathers(text, pad) == {"aggindex": 3}
        # the inner join folds: customer's 1.5 M keys against 2^22 rows
        # of orders a chip
        assert _gathers(text, groups // 4) == {"join/join": 1}
        assert _gathers(text, 1_500_000, "pred") == {"join/join": 1}
        assert len(_collectives(text, "all-gather", 4 * pad)) == 3
        assert not _collectives(text, "all-reduce", groups)


@pytest.mark.slow
@pytest.mark.parametrize("name", ["Q5", "Q10", "Q18"])
def test_fused_join_chain_mesh_program_at_the_sf10_shapes(
        topo, no_persistent_cache, monkeypatch, four_devices, sf10_session,
        name):
    """The three programs of ``tpch_sf10_joins_mesh4.join_stream``
    (``lineitem``'s lanes at 2^26, ``orders``' and the order keys'
    groups at 2^24, ``customer``'s at 2^21): ONE program each over the
    v5e:2x2, no join partitioned (the planner prices a reader's side by
    its scan's rows), no GROUP BY sorted, no sort of a chip's share of
    a bucket, and it fits a chip beside what the other two leave."""
    from tinysql_tpu.executor import devpipe
    from tinysql_tpu.parallel import dist
    monkeypatch.setattr(dist, "broadcast_budget_bytes",
                        lambda: HBM_BYTES * dist.BROADCAST_MEMORY_SHARE)
    progcache.clear()
    monkeypatch.setattr(devpipe, "COMPILED_NODE_KEYS", set())
    text = _compile_mesh_statement(topo, monkeypatch, sf10_session,
                                   tpch.WORKLOAD[name])
    kinds = {k[0] for k in devpipe.COMPILED_NODE_KEYS}
    assert not kinds & {"sortgroup", "joinshuf"}, kinds
    assert "all-to-all" not in text
    assert all(n < (1 << 22) for n in _sorted_lanes(text)), \
        sorted(set(_sorted_lanes(text)))
    if name == "Q5":
        # the view's validity and c_nationkey's halves, at orders' bucket
        assert len([n for n in _all_gathers(text, "join")
                    if n == 1 << 24]) == 3
        # the view's 15 M order keys hold more than half of a chip's
        # 2^24 rows of lineitem: its bvalid[pos] stays; supplier's 100 k
        # keys and, at 2^22 rows of orders a chip, customer's 1.5 M fold
        assert _gathers(text, 1 << 24, "pred") == {"join/join/join": 1}
        assert _gathers(text, 100_000, "pred") == {"join/join": 1}
        assert _gathers(text, 1_500_000, "pred") == \
            {"join/join/join/join": 1}


# ---- the correlated-subquery reports: Q2, Q17 as one fused program each ----

@pytest.fixture(scope="module")
def subq_session():
    """The benchmark's own tables at SF=0.05 (``lineitem``'s bucket 2^19,
    ``partsupp``'s 2^14) and the cell ``tpch_sf1_subq.subq_stream``'s two
    statements as its traffic file spells them."""
    import importlib.util
    from tinysql_tpu.columnar.store import bulk_load
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    mods = {}
    for name in ("datasets/tpch_subq.py", "traffic.py"):
        spec = importlib.util.spec_from_file_location(
            "chip_compile_" + name.replace("/", "_").replace(".", "_"),
            os.path.join(root, "benchmark", name))
        mods[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mods[name])
    data = mods["datasets/tpch_subq.py"]
    with open(os.path.join(root, "benchmark", "traffic",
                           "subq_stream.json")) as f:
        sqls = {st.kind: st.sql
                for st in mods["traffic.py"].expand(json.load(f))}
    ds = data.generate(DEVPIPE_SF, 2_147_483_747)
    s = new_session()
    s.execute(f"create database {data.DATABASE}")
    s.execute(f"use {data.DATABASE}")
    for table, ddl in data.SCHEMAS.items():
        s.execute(ddl)
        bulk_load(s.storage, s.infoschema().table_by_name(data.DATABASE,
                                                          table),
                  ds.tables[table])
    s.execute("set @@tidb_devpipe = 1")
    s.execute("set @@tidb_tpu_min_rows = 0")
    return s, sqls


@pytest.mark.parametrize("name", ["q2", "q17"])
def test_correlated_subquery_reports_compile_as_one_program(
        one_chip, no_persistent_cache, monkeypatch, subq_session, name):
    """Q2 and Q17, their correlated scalar subqueries decorrelated into
    a GROUP BY joined back: ONE program each (the capture raises at the
    first dispatch), compiled inside the joins' budget, fitting the
    chip, with no sort GROUP BY and no sort at all; Q17's comparison is
    the 64-bit integer one (``ops/exactcmp.py``)."""
    import time
    from tinysql_tpu.executor import devpipe
    session, sqls = subq_session
    t0 = time.time()
    progcache.clear()
    monkeypatch.setattr(devpipe, "COMPILED_NODE_KEYS", set())
    fn, abstract = _capture(monkeypatch, session, sqls[name], one_chip)
    text = _compile(fn, *abstract).as_text()
    assert time.time() - t0 < JOIN_COMPILE_BUDGET_S
    kinds = {k[0] for k in devpipe.COMPILED_NODE_KEYS}
    assert {"aggindex", "join", "sel"} <= kinds, kinds
    assert "sortgroup" not in kinds and not _sorted_lanes(text)
    sels = [k for k in devpipe.COMPILED_NODE_KEYS if k[0] == "sel"]
    assert any(key.startswith("exact:<@") for k in sels for key in k[1]) \
        == (name == "q17"), sels
