"""Native runtime library (native/tinysql_native.cpp via ctypes): codec
parity against the pure-python memcomparable codec, hash table parity
against a dict oracle, and engagement of the join fast path.

Skipped wholesale when no C++ toolchain is available (the python paths
remain the semantic reference).
"""
import ctypes
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from tinysql_tpu import native
from tinysql_tpu.codec import keycodec as kc

pytestmark = pytest.mark.skipif(native.lib() is None,
                                reason="native library unavailable")


def test_int_encode_parity():
    vals = np.array([0, 1, -1, 2**63 - 1, -(2**63), 42, -99999],
                    dtype=np.int64)
    enc = native.mc_encode_column(vals, "int")
    for i, v in enumerate(vals):
        b = bytearray()
        kc.encode_int(b, int(v))
        assert bytes(enc[i]) == bytes(b)


def test_uint_encode_parity():
    uv = [0, 1, 2**63, 2**64 - 1, 12345]
    wrapped = np.array(uv, dtype=np.uint64).view(np.int64)
    enc = native.mc_encode_column(wrapped, "uint")
    for i, v in enumerate(uv):
        b = bytearray()
        kc.encode_uint(b, v)
        assert bytes(enc[i]) == bytes(b)


def test_float_encode_parity():
    fv = np.array([0.0, -0.0, 1.5, -1.5, 1e308, -1e308, float("inf"),
                   float("-inf")], dtype=np.float64)
    enc = native.mc_encode_column(fv, "float")
    for i, v in enumerate(fv):
        b = bytearray()
        kc.encode_float(b, float(v))
        assert bytes(enc[i]) == bytes(b)


def test_bytes_roundtrip_parity():
    l = native.lib()
    for data in [b"", b"a", b"a" * 8, b"a" * 9, bytes(range(16)),
                 b"x" * 7, b"\x00\xff" * 5]:
        out = (ctypes.c_uint8 * ((len(data) // 8 + 2) * 9))()
        n = l.mc_encode_bytes(data, ctypes.c_int64(len(data)), out)
        b = bytearray()
        kc.encode_bytes(b, data)
        assert bytes(out[:n]) == bytes(b)[1:]  # python form adds flag byte
        dec = (ctypes.c_uint8 * (len(data) + 16))()
        consumed = ctypes.c_int64()
        dn = l.mc_decode_bytes(bytes(out[:n]), ctypes.c_int64(n), dec,
                               ctypes.byref(consumed))
        assert bytes(dec[:dn]) == data and consumed.value == n


def test_hash_table_oracle():
    rng = np.random.default_rng(7)
    bk = rng.integers(-50, 50, 5000).astype(np.int64)
    bvalid = rng.random(5000) > 0.1
    ht = native.I64HashTable(bk, bvalid)
    pk = rng.integers(-60, 60, 2000).astype(np.int64)
    ids, counts = ht.probe(pk)
    from collections import defaultdict
    m = defaultdict(list)
    for i, k in enumerate(bk):
        if bvalid[i]:
            m[int(k)].append(i)
    pos = 0
    for i, k in enumerate(pk):
        got = sorted(int(x) for x in ids[pos:pos + counts[i]])
        pos += counts[i]
        assert got == sorted(m.get(int(k), [])), i


def test_batch_row_key_parity():
    from tinysql_tpu.codec import tablecodec as tc
    hs = np.array([0, 1, -1, 2**62, 7, -(2**63)], dtype=np.int64)
    for k, h in zip(tc.encode_row_keys_batch(5, hs), hs):
        assert k == tc.encode_row_key(5, int(h))
        assert tc.decode_record_key(k) == (5, int(h))


def test_join_uses_native_path(monkeypatch):
    # assert ENGAGEMENT: the fast path must actually build a native table
    built = []
    orig = native.I64HashTable.__init__

    def spy(self, keys, valid=None):
        built.append(len(keys))
        orig(self, keys, valid)
    monkeypatch.setattr(native.I64HashTable, "__init__", spy)
    from tinysql_tpu.session.session import new_session
    s = new_session()
    s.execute("create database test")
    s.execute("use test")
    s.execute("set @@tidb_use_tpu = 0")
    s.execute("create table a (x int primary key, k int)")
    s.execute("create table b (y int primary key, k int, v varchar(5))")
    s.execute("insert into a values " + ", ".join(
        f"({i}, {i % 5})" for i in range(1, 51)))
    s.execute("insert into b values " + ", ".join(
        f"({i}, {i % 5}, 'v{i}')" for i in range(1, 11)))
    got = s.query("select count(*) from a join b on a.k = b.k").rows
    assert got == [[100]]  # 50 rows x 2 matches each
    assert built, "native I64HashTable was never engaged"
    # left join with NULL keys never matching
    s.execute("insert into a values (99, null)")
    got = s.query("select count(*) from a left join b on a.k = b.k").rows
    assert got == [[101]]
    rows = s.query("select a.x, b.v from a join b on a.k = b.k "
                   "and b.y <= 2 where a.x <= 2 order by a.x, b.v").rows
    assert rows == [["1", "v1"], ["2", "v2"]] or rows == [[1, "v1"], [2, "v2"]]


_RACER = """
import sys, time
sys.path.insert(0, {repo!r})
import numpy as np
from tinysql_tpu import native
native._SO = {so!r}
while time.time() < {go!r}:
    pass
assert native.lib() is not None, "came up without the library"
enc = native.mc_encode_column(np.array([-1, 0, 7], dtype=np.int64), "int")
assert enc.shape == (3, 9) and bytes(enc[1]) == b"\\x03\\x80" + bytes(7)
ids, counts = native.I64HashTable(np.array([5, 6, 5], dtype=np.int64)).probe(
    np.array([5, 9], dtype=np.int64))
assert sorted(ids.tolist()) == [0, 2] and counts.tolist() == [2, 0]
"""


def test_six_processes_on_a_tree_without_the_library_all_get_it(tmp_path):
    """Every xdist worker of a fresh checkout calls lib() at collection:
    the build must be one, and whole when another process loads it."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for name in ("build.py", "tinysql_native.cpp"):
        shutil.copy(os.path.join(here, "native", name), tmp_path / name)
    so = str(tmp_path / "libtinysql_native.so")
    code = _RACER.format(repo=here, so=so, go=time.time() + 2.0)
    procs = [subprocess.Popen([sys.executable, "-c", code],
                              stderr=subprocess.PIPE, text=True)
             for _ in range(6)]
    errs = [p.communicate(timeout=60)[1] for p in procs]
    assert [p.returncode for p in procs] == [0] * 6, errs
    assert [n for n in os.listdir(tmp_path) if n.endswith(".so")] == [
        "libtinysql_native.so"]  # no builder's temporary name is left
