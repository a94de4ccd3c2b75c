"""StringDictionary's two storage forms (materialised in process memory,
blob-backed through plain views) against the per-value binary search and
slice they replaced, which stays here as the reference."""

import sys
import threading

import numpy as np
import pytest

from pinot_tpu.segment import dictionary as dictionary_mod
from pinot_tpu.segment.dictionary import StringDictionary, build_dictionary
from pinot_tpu.spi.data import DataType

STRINGS = ["", "MFGR#1", "MFGR#12", "MFGR#2", "a", "a\x00", "ab", "abc", "b",
           "zürich", "Ünited", "日本", "日本語", "🙂"]
ONE = ["only"]
FORMS = ["materialised", "blob_backed"]
TYPES = [DataType.STRING, DataType.BYTES]


def _values(strings, data_type):
    encoded = sorted(s.encode("utf-8") for s in strings)
    if data_type is DataType.BYTES:
        return encoded
    return [e.decode("utf-8") for e in encoded]


def _mapped(tmp_path, values, data_type):
    """A dictionary as an immutable segment loads it: both arrays mapped."""
    built = build_dictionary(values, data_type)
    np.save(tmp_path / "off.npy", built.offsets)
    np.save(tmp_path / "blob.npy", built.blob)
    return StringDictionary(np.load(tmp_path / "off.npy", mmap_mode="r"),
                            np.load(tmp_path / "blob.npy", mmap_mode="r"),
                            data_type)


@pytest.fixture(params=FORMS)
def form(request, monkeypatch):
    if request.param == "blob_backed":
        monkeypatch.setattr(dictionary_mod, "MATERIALISE_MAX_HOST_BYTES", -1)
    return request.param


def _old_insertion_index_of(d, target: bytes) -> int:
    """The binary search over per-value slices of the arrays, as it was."""
    def raw(i):
        return d.blob[int(d.offsets[i]):int(d.offsets[i + 1])].tobytes()

    lo, hi = 0, len(d)
    while lo < hi:
        mid = (lo + hi) // 2
        if raw(mid) < target:
            lo = mid + 1
        else:
            hi = mid
    if lo < len(d) and raw(lo) == target:
        return lo
    return -(lo + 1)


@pytest.mark.parametrize("data_type", TYPES, ids=lambda t: t.name)
@pytest.mark.parametrize("strings", [STRINGS, ONE], ids=["many", "one"])
def test_reads_equal_the_values_written(tmp_path, form, data_type, strings):
    values = _values(strings, data_type)
    d = _mapped(tmp_path, values, data_type)
    assert d.blob_backed == (form == "blob_backed")
    assert len(d) == d.cardinality == len(values)
    assert d.get_values(range(len(values))) == values
    assert [d.get_value(i) for i in range(len(values))] == values
    assert d.get_values(np.array([len(values) - 1, 0, 0], np.int32)) == [
        values[-1], values[0], values[0]]
    assert d.get_values([]) == []
    assert type(d.get_value(0)) is type(values[0])
    assert (d.min_value, d.max_value) == (values[0], values[-1])
    assert (d.host_bytes > 0) == (form == "materialised")


@pytest.mark.parametrize("data_type", TYPES, ids=lambda t: t.name)
def test_search_agrees_with_the_old_binary_search(tmp_path, form, data_type):
    values = _values(STRINGS[1:], data_type)  # "" is absent: below the first
    d = _mapped(tmp_path, values, data_type)
    probes = values + _values(["", "MFGR#11", "abcd", "zz", "日", "🙂🙂"],
                              data_type)
    for p in probes:
        want = _old_insertion_index_of(
            d, p if isinstance(p, bytes) else p.encode("utf-8"))
        assert d.insertion_index_of(p) == want, p
        assert d.index_of(p) == (want if want >= 0 else -1), p
    assert d.insertion_index_of(probes[len(values)]) == -1  # below the first
    assert d.insertion_index_of(probes[-1]) == -(len(values) + 1)  # above


@pytest.mark.parametrize("lo_inclusive", [True, False])
@pytest.mark.parametrize("hi_inclusive", [True, False])
def test_range_to_dict_id_interval_corners(tmp_path, form, lo_inclusive,
                                           hi_inclusive):
    values = _values(STRINGS, DataType.STRING)
    d = _mapped(tmp_path, values, DataType.STRING)

    def ids(lo, hi):
        a, b = d.range_to_dict_id_interval(lo, hi, lo_inclusive, hi_inclusive)
        return list(range(a, b + 1))

    def want(lo, hi):
        def keep(v):
            e = v.encode("utf-8")
            above = lo is None or (e >= lo.encode() if lo_inclusive
                                   else e > lo.encode())
            below = hi is None or (e <= hi.encode() if hi_inclusive
                                   else e < hi.encode())
            return above and below
        return [i for i, v in enumerate(values) if keep(v)]

    for lo, hi in [("MFGR#12", "b"), ("MFGR#10", "aa"), (None, "ab"),
                   ("abc", None), ("", "🙂"), ("zz", "zzz"), ("b", "a")]:
        assert ids(lo, hi) == want(lo, hi), (lo, hi)


def test_no_memmap_method_runs_for_a_value(tmp_path, form, monkeypatch):
    values = [f"MFGR#{i:04d}" for i in range(500)]
    d = _mapped(tmp_path, values, DataType.STRING)
    assert isinstance(d.offsets, np.memmap) and isinstance(d.blob, np.memmap)
    calls = []
    for name in ("__getitem__", "__array_finalize__", "__array_wrap__"):
        def counted(self, *a, _old=getattr(np.memmap, name), **kw):
            calls.append(name)
            return _old(self, *a, **kw)
        monkeypatch.setattr(np.memmap, name, counted)
    assert d.get_values(range(500)) == values
    assert d.get_value(7) == values[7]
    assert [d.index_of(v) for v in values[::50]] == list(range(0, 500, 50))
    assert d.range_to_dict_id_interval("MFGR#0100", "MFGR#0200x",
                                       True, True) == (100, 200)
    assert calls == []


def test_eight_threads_materialise_one_dictionary(tmp_path):
    values = [f"v{i:05d}" for i in range(4000)]
    d = _mapped(tmp_path, values, DataType.STRING)
    start = threading.Barrier(8)
    seen = [None] * 8

    def read(k):
        start.wait(timeout=30)
        seen[k] = (d.get_values(range(len(values))), d.index_of("v02000"))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=read, args=(k,)) for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert all(s == (values, 2000) for s in seen)
    assert d.host_bytes > 0
