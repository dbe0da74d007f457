"""bucket()/pad1() edge cases and padded-row inertness.

Every device kernel pads its inputs to a power-of-two bucket with a
validity mask; these tests pin the bucket function's edges (n=0, n=1,
exact powers of two, growth monotonicity) and prove padding rows stay
INERT through the valid mask for the hash-agg and topk/sort kernels —
the invariant the async block pipeline's per-block padding rides on.
"""
import numpy as np
import pytest

from tinysql_tpu.ops import kernels


# ---- bucket() ------------------------------------------------------------

def test_bucket_edges():
    assert kernels.bucket(0) == 16
    assert kernels.bucket(1) == 16
    assert kernels.bucket(15) == 16
    assert kernels.bucket(16) == 16       # exact power of two: no growth
    assert kernels.bucket(17) == 32


def test_bucket_exact_powers_fixed():
    for k in range(4, 22):
        assert kernels.bucket(2 ** k) == 2 ** k
        assert kernels.bucket(2 ** k + 1) == 2 ** (k + 1)


def test_bucket_growth_monotone():
    prev = 0
    for n in range(0, 4100):
        b = kernels.bucket(n)
        assert b >= max(n, 16)
        assert b >= prev, (n, b, prev)  # buckets never shrink as n grows
        prev = b


# ---- pad1() --------------------------------------------------------------

def test_pad1_empty_input():
    out = kernels.pad1(np.empty(0, dtype=np.int64), 16)
    assert out.shape == (16,) and (out == 0).all()
    outb = kernels.pad1(np.empty(0, dtype=bool), 16, True)
    assert outb.dtype == bool and outb.all()


def test_pad1_single_row():
    out = kernels.pad1(np.array([7], dtype=np.int64), 16)
    assert out[0] == 7 and (out[1:] == 0).all()


def test_pad1_exact_bucket_is_identity():
    a = np.arange(16, dtype=np.int64)
    assert kernels.pad1(a, 16) is a  # no copy when already bucket-sized


def test_pad1_fill_value():
    out = kernels.pad1(np.array([1.5]), 4, fill=np.inf)
    assert out[0] == 1.5 and np.isinf(out[1:]).all()


# ---- padding rows are inert through the valid mask -----------------------

def _group_ref(keys, vals):
    out = {}
    for k, v in zip(keys, vals):
        s, c = out.get(k, (0.0, 0))
        out[k] = (s + v, c + 1)
    return out


def test_hash_agg_padding_inert():
    # n=5 in a 16-bucket: 11 padding rows must contribute to NO group
    keys = np.array([1, 1, 2, 2, 2], dtype=np.int64)
    kn = np.zeros(5, dtype=bool)
    vals = np.array([1.0, 2.0, 3.0, 4.0, 5.0])
    vn = np.zeros(5, dtype=bool)
    out_keys, out_aggs, _first = kernels.group_aggregate(
        [(keys, kn)], [("sum", True), ("count", True)],
        [(vals, vn), (vals, vn)], 5)
    got_k = np.asarray(out_keys[0][0])
    ref = _group_ref(keys, vals)
    assert sorted(got_k.tolist()) == sorted(ref)
    for k, s, c in zip(got_k, np.asarray(out_aggs[0][0]),
                       np.asarray(out_aggs[1][0])):
        assert (s, c) == ref[int(k)], (k, s, c)


def test_hash_agg_padding_inert_exact_bucket():
    # n == bucket exactly: zero padding rows, same answer
    n = 16
    keys = np.arange(n, dtype=np.int64) % 3
    vals = np.ones(n)
    zb = np.zeros(n, dtype=bool)
    out_keys, out_aggs, _ = kernels.group_aggregate(
        [(keys, zb)], [("count", True)], [(vals, zb)], n)
    counts = dict(zip(np.asarray(out_keys[0][0]).tolist(),
                      np.asarray(out_aggs[0][0]).tolist()))
    assert counts == {0: 6, 1: 5, 2: 5}


def test_hash_agg_filter_mask_excludes_rows():
    # the valid mask is the SAME lane padding rides: masked-off real rows
    # must vanish exactly like padding does
    keys = np.array([1, 1, 2], dtype=np.int64)
    vals = np.array([10.0, 20.0, 30.0])
    zb = np.zeros(3, dtype=bool)
    mask = np.array([True, False, True])
    out_keys, out_aggs, _ = kernels.group_aggregate(
        [(keys, zb)], [("sum", True)], [(vals, zb)], 3, filter_mask=mask)
    got = dict(zip(np.asarray(out_keys[0][0]).tolist(),
                   np.asarray(out_aggs[0][0]).tolist()))
    assert got == {1: 10.0, 2: 30.0}


def test_topk_sort_padding_inert():
    # k far beyond n: only real rows may surface (padding carries the
    # worst-score sentinel and must never win a slot)
    v = np.array([5.0, 1.0, 3.0])
    m = np.zeros(3, dtype=bool)
    ids = np.asarray(kernels.top_k([(v, m)], [False], 3, 10))
    assert ids.tolist() == [1, 2, 0]      # ascending, all 3, nothing else
    ids_d = np.asarray(kernels.top_k([(v, m)], [True], 3, 2))
    assert ids_d.tolist() == [0, 2]


def test_sort_permutation_padding_inert():
    # n=1 in a 16-bucket: the permutation is exactly [0]
    v = np.array([42], dtype=np.int64)
    m = np.zeros(1, dtype=bool)
    perm = np.asarray(kernels.sort_permutation([(v, m)], [False], 1))
    assert perm.tolist() == [0]
    # multi-key, n below bucket: a permutation of range(n) exactly
    a = np.array([2, 1, 2, 1, 0], dtype=np.int64)
    b = np.array([1.0, 2.0, 0.5, 1.0, 9.0])
    z = np.zeros(5, dtype=bool)
    perm = np.asarray(kernels.sort_permutation([(a, z), (b, z)],
                                               [False, True], 5))
    assert sorted(perm.tolist()) == [0, 1, 2, 3, 4]
    assert perm.tolist() == sorted(
        range(5), key=lambda i: (a[i], -b[i]))


# ---- prefix_sum() / lex_head() -------------------------------------------

def test_prefix_sum_matches_cumsum_across_the_chunk_boundary():
    """Below PREFIX_CHUNKS a tree scan, above it chunks scanned in
    lockstep: both are the inclusive prefix sum — exact for int64, to
    float rounding for float64 (the order of addition differs)."""
    j = kernels.jax()
    rng = np.random.default_rng(3)
    for n in (16, kernels.PREFIX_CHUNKS, 4 * kernels.PREFIX_CHUNKS):
        xi = rng.integers(-1 << 40, 1 << 40, n)
        np.testing.assert_array_equal(
            np.asarray(j.jit(kernels.prefix_sum)(xi)), np.cumsum(xi))
        xf = rng.uniform(0.0, 1e5, n)
        np.testing.assert_allclose(
            np.asarray(j.jit(kernels.prefix_sum)(xf)), np.cumsum(xf),
            rtol=1e-12)


def test_lex_head_is_the_head_of_a_stable_lexsort():
    """Selection (k <= LEX_SELECT_MAX) and the full-sort fallback agree
    with numpy's lexsort: last operand primary, ties to the lowest row,
    infinities in order."""
    j = kernels.jax()
    rng = np.random.default_rng(1)
    big = kernels.LEX_SELECT_MAX + 16
    for n, k in ((16, 16), (1024, 16), (4096, 64), (100, 7), (256, big)):
        ops = [rng.integers(0, 5, n).astype(np.int64),
               rng.choice([1.5, -2.0, np.inf, -np.inf, 0.0, 3.25], n),
               rng.integers(0, 2, n).astype(np.int8)]
        got = np.asarray(j.jit(lambda o: kernels.lex_head(o, k))(ops))
        np.testing.assert_array_equal(got, np.lexsort(ops)[:k])


# ---- compile-cache placement ---------------------------------------------

_CACHE_PROBE = """
import json, sys
from tinysql_tpu.ops import kernels
before = kernels._cache_dir()
jax = kernels.jax()
in_jax = jax.config.jax_compilation_cache_dir
jax.devices()                       # the backend exists from here on
after_backend = kernels._cache_dir()
from tinysql_tpu.session.session import new_session
s = new_session()
s.execute("set @@tidb_compile_cache_dir = %r")
print(json.dumps({"before": before, "in_jax": in_jax,
                  "after_backend": after_backend,
                  "after_set": jax.config.jax_compilation_cache_dir,
                  "warnings": [w[2] for w in s.last_warnings]}))
"""


@pytest.fixture(scope="module")
def cache_probe(tmp_path_factory):
    """``probe(env_dir)`` -> what a FRESH process saw (jax reads
    JAX_COMPILATION_CACHE_DIR as it is imported, and the backend is not
    yet there), one child per distinct ``env_dir``."""
    import functools
    import json
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    by_sysvar = str(tmp_path_factory.mktemp("cache") / "by-sysvar")

    @functools.lru_cache(maxsize=None)
    def probe(env_dir: str) -> dict:
        env = dict(os.environ, PYTHONPATH=repo)  # conftest pins cpu for it
        env.pop(kernels.CACHE_DIR_ENV, None)
        if env_dir:
            env[kernels.CACHE_DIR_ENV] = env_dir
        r = subprocess.run(
            [sys.executable, "-c", _CACHE_PROBE % by_sysvar],
            capture_output=True, text=True, timeout=120, env=env, cwd=repo)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])
    probe.repo, probe.by_sysvar = repo, by_sysvar
    return probe


def test_cache_dir_from_the_standard_variable_wins(cache_probe, tmp_path):
    """Variable set: jax keeps its cache there, the engine sets none in
    code, and a SET of the sysvar changes nothing and says which won."""
    want = str(tmp_path / "from-env")
    got = cache_probe(want)
    assert got["before"] == got["in_jax"] == got["after_backend"] \
        == got["after_set"] == want
    assert len(got["warnings"]) == 1
    assert kernels.CACHE_DIR_ENV in got["warnings"][0]
    assert want in got["warnings"][0]


def test_cache_dir_default_is_one_fixed_path(cache_probe):
    """Variable unset: <repo>/.jax_cache — the same before and after the
    backend exists, so two processes share it."""
    import os
    got = cache_probe("")
    assert got["before"] == got["in_jax"] == got["after_backend"] \
        == os.path.join(cache_probe.repo, ".jax_cache")


def test_cache_dir_sysvar_moves_it_when_the_variable_is_unset(cache_probe):
    got = cache_probe("")
    assert got["after_set"] == cache_probe.by_sysvar
    assert got["warnings"] == []
