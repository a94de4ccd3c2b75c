"""graftlint: the tier-1 invariant gate + per-checker negative fixtures.

Two layers:

- ``test_package_is_clean`` runs every checker family over the whole
  ``pinot_tpu`` package with the checked-in baseline — the machine-enforced
  gate that keeps the PR-1..3 bug classes (field touched outside its
  guarding lock, acquire without a paired release, host effects in traced
  code, stat added but never wired) from coming back. PR 5 adds the
  dataflow families: kernel param protocol (``protocol``), device-sync
  taint (``sync``), and HBM accounting conservation (``conservation``).
- the fixture tests seed one violation of each invariant into a temp file
  and prove the checker catches it — including a regression fixture in the
  exact shape of the PR-2 ``stage()`` get-then-set race, an unpaired-lease
  fixture, and (for the protocol family) a scratch copy of
  ``pallas_kernels.py`` with one ``pc.take()`` reordered.

``pytest -m lint`` runs just this module (fast: stdlib ast only, no jax
work beyond the conftest import).
"""

import json
import os
import textwrap

import pytest

import pinot_tpu
from pinot_tpu.tools.lint import run_lint
from pinot_tpu.tools.lint.__main__ import main as lint_main
from pinot_tpu.tools.lint.core import DEFAULT_BASELINE

pytestmark = pytest.mark.lint

PKG = os.path.dirname(os.path.abspath(pinot_tpu.__file__))


def _lint(tmp_path, source, name="fixture.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    new, _accepted = run_lint([str(p)])
    return new


def _by_checker(findings, checker):
    return [f for f in findings if f.checker == checker]


# --------------------------------------------------------------------------
# the gate
# --------------------------------------------------------------------------

def test_package_is_clean():
    """The whole package passes every checker family against the
    checked-in (ideally empty) baseline. A finding here means either fix
    the code or — rarely, with justification — baseline it."""
    new, accepted = run_lint([PKG], baseline=DEFAULT_BASELINE)
    assert not new, "graftlint findings:\n" + "\n".join(
        f.render() for f in new)


def test_baseline_is_empty():
    """The dataflow families ship with a truly empty baseline: every true
    positive they found at landing time was fixed, not accepted."""
    with open(DEFAULT_BASELINE, encoding="utf-8") as f:
        assert json.load(f)["entries"] == []


def test_cli_exit_codes(tmp_path):
    """CI contract: non-zero exit iff there are non-baselined findings."""
    assert lint_main([PKG]) == 0
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""\
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._d = {}  # guarded-by: _lock

            def peek(self):
                return self._d.get("k")
        """))
    assert lint_main([str(bad)]) == 1


# --------------------------------------------------------------------------
# lock discipline
# --------------------------------------------------------------------------

def test_lock_guard_catches_unguarded_access(tmp_path):
    new = _lint(tmp_path, """\
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._d = {}  # guarded-by: _lock

            def ok(self):
                with self._lock:
                    return self._d.get("k")

            def bad_read(self):
                return self._d.get("k")

            def bad_write(self, v):
                self._d["k"] = v
        """)
    got = {(f.symbol, "read" in f.message) for f in _by_checker(new,
                                                               "lock-guard")}
    assert ("C._d:bad_read", True) in got
    assert ("C._d:bad_write", False) in got
    assert not any("ok" in f.symbol for f in new)


def test_lock_guard_regression_stage_get_then_set(tmp_path):
    """The PR-2 ``stage()`` shape: optimistic get outside the lock, insert
    inside it. Two concurrent stagers both miss and build duplicate device
    arrays; the loser's set leaks until GC. The checker must flag the
    unguarded read."""
    new = _lint(tmp_path, """\
        import threading

        class Cache:
            def __init__(self):
                self._lock = threading.Lock()
                self._cached = {}  # guarded-by: _lock

            def stage(self, name):
                e = self._cached.get(name)
                if e is None:
                    e = object()
                    with self._lock:
                        self._cached[name] = e
                return e
        """)
    reads = [f for f in _by_checker(new, "lock-guard")
             if f.symbol == "Cache._cached:stage" and "read" in f.message]
    assert reads, [f.render() for f in new]


def test_lock_guard_writes_only_mode_and_closures(tmp_path):
    """``guarded-by-writes`` permits lock-free reads but still flags
    unguarded mutation; a closure does NOT inherit the enclosing ``with``
    (it runs later, on another thread)."""
    new = _lint(tmp_path, """\
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._d = {}  # guarded-by-writes: _lock

            def lockfree_read(self):
                return self._d.get("k")

            def bad_write(self, v):
                self._d["k"] = v

            def bad_closure(self):
                with self._lock:
                    return lambda v: self._d.update(v)
        """)
    syms = {f.symbol for f in _by_checker(new, "lock-guard")}
    assert "C._d:bad_write" in syms
    assert "C._d:bad_closure" in syms
    assert not any("lockfree_read" in s for s in syms)


def test_lock_guard_inherited_lock_and_locked_suffix(tmp_path):
    """A base-class lock guards subclass fields; ``*_locked`` methods
    assert caller-holds-the-lock and are exempt."""
    new = _lint(tmp_path, """\
        import threading

        class Base:
            def __init__(self):
                self._lock = threading.Lock()

        class Sub(Base):
            def __init__(self):
                super().__init__()
                self._d = {}  # guarded-by: _lock

            def _pick_locked(self):
                return self._d.get("k")

            def ok(self):
                with self._lock:
                    return self._pick_locked()
        """)
    assert not new, [f.render() for f in new]


def test_lock_order_catches_inversion(tmp_path):
    new = _lint(tmp_path, """\
        import threading

        class A:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def ab(self):
                with self._a:
                    with self._b:
                        pass

            def ba(self):
                with self._b:
                    with self._a:
                        pass
        """)
    inv = _by_checker(new, "lock-order")
    assert len(inv) == 1 and "A._a" in inv[0].symbol \
        and "A._b" in inv[0].symbol


def test_lock_order_follows_calls(tmp_path):
    """The inversion hides behind a call: holding A, call a method that
    takes B; holding B, call one that takes A."""
    new = _lint(tmp_path, """\
        import threading

        class M:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def take_b(self):
                with self._b:
                    pass

            def take_a(self):
                with self._a:
                    pass

            def ab(self):
                with self._a:
                    self.take_b()

            def ba(self):
                with self._b:
                    self.take_a()
        """)
    assert _by_checker(new, "lock-order")


# --------------------------------------------------------------------------
# resource pairing
# --------------------------------------------------------------------------

def test_pairing_catches_unpaired_lease(tmp_path):
    """The unpaired-lease shape: ``end_query`` exists but only on the
    fall-through path — an exception in between leaks the lease's pins
    (and under admission pressure, pinned bytes never unpin)."""
    new = _lint(tmp_path, """\
        def leaky(mgr, segments, run):
            lease = mgr.begin_query(segments, [])
            out = run(segments)
            mgr.end_query(lease)
            return out
        """)
    pf = _by_checker(new, "pairing")
    assert len(pf) == 1
    assert "finally" in pf[0].message and "begin_query" in pf[0].symbol


def test_pairing_catches_missing_and_discarded_release(tmp_path):
    new = _lint(tmp_path, """\
        def never_released(mgr, segments, run):
            lease = mgr.begin_query(segments, [])
            return run(segments, lease)

        def discarded(mgr, segments):
            mgr.begin_query(segments, [])
        """)
    msgs = [f.message for f in _by_checker(new, "pairing")]
    # `lease` escapes through run(...) -> the caller's job; only the
    # discarded acquire is a local certainty
    assert len(msgs) == 1 and "discarded" in msgs[0]


def test_pairing_catches_unreleased_lease_on_reject(tmp_path):
    """The admission-reject leak shape (PR 7): a gate that can raise
    between ``begin_query``/``admit`` and the fall-through releases means
    every rejection leaks a lease pin AND a gate slot. Both releases live
    only on the fall-through path — the checker must flag both halves."""
    new = _lint(tmp_path, """\
        def rejected_leaks(mgr, gate, segments, run):
            lease = mgr.begin_query(segments, [])
            ticket = gate.admit("t")
            out = run(segments)
            mgr.end_query(lease)
            gate.release(ticket)
            return out
        """)
    pf = _by_checker(new, "pairing")
    assert len(pf) == 2, [f.render() for f in pf]
    symbols = {f.symbol for f in pf}
    assert "rejected_leaks:begin_query" in symbols
    assert "rejected_leaks:admit" in symbols
    assert all("finally" in f.message for f in pf)


def test_pairing_accepts_admission_gate_shape(tmp_path):
    """The correct executor shape: admit -> try -> lease inside ->
    releases in finally, rejection before the lease ever opens."""
    new = _lint(tmp_path, """\
        def admitted(mgr, gate, segments, run):
            ticket = gate.admit("t")
            try:
                lease = mgr.begin_query(segments, [])
                try:
                    return run(segments)
                finally:
                    mgr.end_query(lease)
            finally:
                gate.release(ticket)
        """)
    assert not _by_checker(new, "pairing")


def test_pairing_accepts_finally_and_context_manager(tmp_path):
    new = _lint(tmp_path, """\
        def safe(mgr, segments, run):
            lease = mgr.begin_query(segments, [])
            try:
                return run(segments)
            finally:
                mgr.end_query(lease)

        def acquired(tdm, run):
            sdms = tdm.acquire_segments(None)
            try:
                return run(sdms)
            finally:
                tdm.release_segments(sdms)
        """)
    assert not _by_checker(new, "pairing")


def test_pairing_catches_unpaired_segment_acquire(tmp_path):
    """Release on the fall-through path only. (Passing the acquired list
    into another call would make it escape — the checker is conservative —
    so the work here is local.)"""
    new = _lint(tmp_path, """\
        def leaky(tdm):
            sdms = tdm.acquire_segments(None)
            total = 0
            for s in sdms:
                total += s.segment.num_docs
            tdm.release_segments(sdms)
            return total
        """)
    assert _by_checker(new, "pairing")


# --------------------------------------------------------------------------
# tracer safety
# --------------------------------------------------------------------------

def test_tracer_catches_host_effects_in_jit_reachable_code(tmp_path):
    """Roots via decorator AND call-site; the denylisted call sits one
    call-graph hop below the root."""
    new = _lint(tmp_path, """\
        import time
        import jax


        def helper(x):
            return x + time.time()


        @jax.jit
        def decorated(x):
            return helper(x)


        def kernel(x):
            return float(x) + 1.0


        def build():
            return jax.jit(kernel)
        """)
    tf = _by_checker(new, "tracer")
    msgs = " | ".join(f.message for f in tf)
    assert "time.time" in msgs                      # transitively reached
    assert any("float" in f.symbol for f in tf)     # cast on traced param


def test_tracer_catches_item_and_global_mutation(tmp_path):
    new = _lint(tmp_path, """\
        import jax

        _CACHE = {}


        def kernel(x):
            _CACHE[int(x.shape[0])] = 1
            return x.sum().item()


        out = jax.jit(kernel)
        """)
    syms = {f.symbol for f in _by_checker(new, "tracer")}
    assert "kernel:item" in syms
    assert "kernel:mutate:_CACHE" in syms


def test_tracer_ignores_untraced_functions(tmp_path):
    new = _lint(tmp_path, """\
        import time


        def host_side(x):
            return x + time.time()
        """)
    assert not _by_checker(new, "tracer")


# --------------------------------------------------------------------------
# wire / config consistency
# --------------------------------------------------------------------------

WIRE_FIXTURE = """\
    from dataclasses import dataclass, field


    @dataclass
    class QueryStats:
        num_docs: int = 0
        forgotten: int = 0

        def to_dict(self):
            return {"numDocsScanned": self.num_docs}

        def merge(self, other):
            self.num_docs += other.num_docs


    def _stats_from_dict(st):
        return QueryStats(num_docs=st.get("numDocsScanned", 0))
    """


def test_wire_catches_stat_missing_from_wire(tmp_path):
    """The 'added a stat, forgot the wire' drift: ``forgotten`` rides
    neither to_dict nor merge nor the decode side."""
    new = _lint(tmp_path, WIRE_FIXTURE)
    syms = {f.symbol for f in _by_checker(new, "wire")}
    assert "QueryStats.forgotten:to_dict" in syms
    assert "QueryStats.forgotten:merge" in syms
    assert "QueryStats.forgotten:_stats_from_dict" in syms
    assert not any("num_docs" in s for s in syms)


def test_wire_catches_launch_key_merge_disagreement(tmp_path):
    new = _lint(tmp_path, """\
        LAUNCH_MAX_KEYS = ("batchSize", "notMerged")


        class QueryStats:
            def to_dict(self):
                return {}

            def merge(self, other):
                key = "batchSize"
                return key
        """)
    syms = {f.symbol for f in _by_checker(new, "wire")}
    assert "LAUNCH_MAX_KEYS.notMerged" in syms
    assert "LAUNCH_MAX_KEYS.batchSize" not in syms


COLKIND_FIXTURE = """\
    _COL_I64 = 0
    _COL_STR = 2
    _COL_NEW = 7


    def _encode_column(out, vals):
        out.append(_COL_I64)


    def _decode_column(buf, off, n):
        kind = buf[off]
        if kind == _COL_I64:
            return [], off
        if kind == _COL_STR:
            return [], off
        if kind == _COL_NEW:
            return [], off
        raise ValueError(kind)


    def take_boxed(col):
        if col.kind == _COL_I64:
            return list(col.arr)
        if col.kind == _COL_STR:
            return col.strings()
        raise ValueError(col.kind)


    def single_kind_helper(col):
        return col.kind == _COL_STR
    """


def test_wire_colkind_partial_dispatch_flagged(tmp_path):
    """A new column kind (_COL_NEW) that encode and a columns() consumer
    don't handle is flagged; the full decode dispatch and the single-kind
    helper are clean."""
    new = _lint(tmp_path, COLKIND_FIXTURE)
    syms = {f.symbol for f in _by_checker(new, "wire")}
    assert "colkind._encode_column" in syms
    assert "colkind.take_boxed" in syms
    assert "colkind._decode_column" not in syms
    assert "colkind.single_kind_helper" not in syms


def test_wire_colkind_full_dispatch_clean(tmp_path):
    new = _lint(tmp_path, """\
        _COL_I64 = 0
        _COL_OBJ = 3
        _COL_NUMERIC = (_COL_I64,)


        def _encode_column(out, vals):
            out.append(_COL_I64 if vals else _COL_OBJ)


        def _decode_column(buf, off, n):
            return {_COL_I64: 1, _COL_OBJ: 2}[buf[off]], off


        def grouping_helper(col):
            return col.kind in _COL_NUMERIC
        """)
    assert not _by_checker(new, "wire")


def test_config_catches_undeclared_key(tmp_path):
    new = _lint(tmp_path, """\
        class CommonConstants:
            DECLARED = "pinot.server.query.declared.knob"


        def read(cfg):
            a = cfg.get("pinot.server.query.declared.knob")
            b = cfg.get("pinot.server.query.bogus.knob")
            return a, b
        """)
    cf = _by_checker(new, "config")
    assert [f.symbol for f in cf] == ["pinot.server.query.bogus.knob"]


# --------------------------------------------------------------------------
# kernel param protocol (dataflow tier)
# --------------------------------------------------------------------------

PROTO_TABLE = """\
    _FILTER_PARAMS = {"eq": 1, "range": 2, "lut": 1}


"""


def test_protocol_catches_missing_take(tmp_path):
    """The consumer takes fewer params than the table declares for an op:
    every later predicate reads the WRONG array — silently wrong results."""
    new = _lint(tmp_path, PROTO_TABLE + """\
    def _emit(spec, pc):
        op = spec[0]
        if op == "eq":
            return pc.take()
        if op == "range":
            lo = pc.take()  # table says 2: the hi bound is never taken
            return lo
        if op == "lut":
            return pc.take()
        raise AssertionError(op)
    """)
    syms = {f.symbol for f in _by_checker(new, "protocol")}
    assert "_emit:range" in syms, [f.render() for f in new]
    assert not any(s.endswith(":eq") or s.endswith(":lut") for s in syms)


def test_protocol_catches_extra_take(tmp_path):
    new = _lint(tmp_path, PROTO_TABLE + """\
    def _emit(spec, pc):
        op = spec[0]
        if op == "eq":
            return pc.take() + pc.take()  # table says 1
        if op == "range":
            lo, hi = pc.take(), pc.take()
            return lo + hi
        if op == "lut":
            return pc.take()
        raise AssertionError(op)
    """)
    syms = {f.symbol for f in _by_checker(new, "protocol")}
    assert "_emit:eq" in syms
    assert not any(s.endswith(":range") for s in syms)


def test_protocol_raise_declines_an_op(tmp_path):
    """A consumer that raises for an op declines it (the pallas extractor's
    ``_Ineligible`` contract) — no finding for ops it never claims."""
    new = _lint(tmp_path, PROTO_TABLE + """\
    def _emit(spec, pc):
        op = spec[0]
        if op == "eq":
            return pc.take()
        raise ValueError(op)  # range/lut: declined, another rung serves
    """)
    assert not _by_checker(new, "protocol"), [f.render() for f in new]


def test_protocol_catches_reordered_group_takes(tmp_path):
    """The classic silent-wrong-results drift: the pack side writes
    (strides, bases) but a consumer takes (bases, strides) — every grouped
    result mis-keys."""
    new = _lint(tmp_path, """\
        def pack(params, strides, group_bases):
            params.append(strides)
            params.append(group_bases)

        def consume(pc):
            bases = pc.take()
            strides = pc.take()
            return strides, bases
        """)
    hits = [f for f in _by_checker(new, "protocol")
            if "group-order" in f.symbol]
    assert hits and "consume" in hits[0].symbol


def test_protocol_pack_side_drift(tmp_path):
    """The pack side appends a different count than the table declares for
    the op its return tuple carries."""
    new = _lint(tmp_path, PROTO_TABLE + """\
    def _compile(pred, params):
        op = pred[0]
        if op == "eq":
            params.append(pred[1])
            params.append(pred[2])  # one too many: table says 1
            return ("eq", pred[1])
        if op == "range":
            params.append(pred[1])
            params.append(pred[2])
            return ("range", pred[1])
        raise ValueError(op)
    """)
    syms = {f.symbol for f in _by_checker(new, "protocol")}
    assert "_compile:pack:eq" in syms
    assert not any(s.endswith("pack:range") for s in syms)


def test_protocol_flags_reordered_take_in_pallas_scratch(tmp_path):
    """Acceptance fixture: a scratch copy of the REAL pallas_kernels.py
    with the strides/bases ``pc.take()`` pair swapped must produce a
    protocol finding against the real plan.py pack order; the unmodified
    pair is clean."""
    eng = os.path.join(PKG, "engine")
    with open(os.path.join(eng, "plan.py"), encoding="utf-8") as f:
        plan_src = f.read()
    with open(os.path.join(eng, "pallas_kernels.py"),
              encoding="utf-8") as f:
        pk_src = f.read()
    s_line = "strides = [int(s) for s in np.asarray(pc.take())]"
    b_line = "bases = [int(b) for b in np.asarray(pc.take())]"
    assert s_line in pk_src and b_line in pk_src, \
        "pallas_kernels group-take lines moved; update the fixture"
    swapped = (pk_src.replace(s_line, "@@SWAP@@")
               .replace(b_line, s_line)
               .replace("@@SWAP@@", b_line))
    (tmp_path / "plan.py").write_text(plan_src)
    (tmp_path / "pallas_kernels.py").write_text(swapped)
    new, _ = run_lint([str(tmp_path)])
    hits = [f for f in _by_checker(new, "protocol")
            if "group-order" in f.symbol]
    assert hits, [f.render() for f in new]

    (tmp_path / "pallas_kernels.py").write_text(pk_src)
    clean, _ = run_lint([str(tmp_path)])
    assert not _by_checker(clean, "protocol"), \
        [f.render() for f in clean]


# --------------------------------------------------------------------------
# device-sync taint (dataflow tier)
# --------------------------------------------------------------------------

def test_sync_catches_materialization_under_lock(tmp_path):
    """float() on a device value inside ``with self._lock`` blocks every
    thread queuing on the lock until the device program finishes — the
    convoy PR 3 removed the global combine lock to escape."""
    new = _lint(tmp_path, """\
        import threading

        import jax.numpy as jnp


        class Accum:
            def __init__(self):
                self._lock = threading.Lock()
                self.total = 0.0

            def add(self, x):
                dev = jnp.sum(x)
                with self._lock:
                    self.total += float(dev)

            def add_ok(self, x):
                host = float(jnp.sum(x))  # sync BEFORE taking the lock
                with self._lock:
                    self.total += host
        """)
    sf = _by_checker(new, "sync")
    assert any("Accum.add" in f.symbol and "float()" in f.symbol
               for f in sf), [f.render() for f in new]
    assert not any("add_ok" in f.symbol for f in sf)


def test_sync_catches_dispatcher_thread_materialization(tmp_path):
    """An implicit D2H on the per-mesh dispatcher thread stalls EVERY
    sharded launch in the process, not one query."""
    new = _lint(tmp_path, """\
        import threading

        import jax.numpy as jnp
        import numpy as np


        class Dispatcher:
            def start(self):
                self._t = threading.Thread(target=self._loop, daemon=True)
                self._t.start()

            def _loop(self):
                dev = jnp.zeros(4)
                return np.asarray(dev)  # blocks the dispatcher on device
        """, name="mini_launcher.py")
    sf = _by_checker(new, "sync")
    assert any("_loop" in f.symbol and "asarray" in f.symbol
               for f in sf), [f.render() for f in new]


def test_sync_catches_gauge_callback_materialization(tmp_path):
    """Gauge/telemetry callbacks registered via ``MetricsRegistry.gauge``
    (or ``Telemetry.track_gauge``) run on scrape/sampler threads: a
    device sink inside one silently stalls every /metrics pull on device
    execution. Both the lambda-closure and named-function registration
    shapes must flag; an int-only gauge stays clean."""
    new = _lint(tmp_path, """\
        import jax.numpy as jnp
        import numpy as np


        class Exporter:
            def __init__(self, registry):
                self._staged = jnp.zeros(8)
                self.depth = 3
                dev = jnp.sum(self._staged)
                # BAD: the lambda closes over a device value and
                # materializes it at scrape time
                registry.gauge("staged_total", lambda: float(dev))
                # OK: plain host int
                registry.gauge("queue_depth", lambda: float(self.depth))

            def bind(self, registry):
                # BAD: named callback sinking a device value per scrape
                registry.gauge("staged_sum", self._read_total)

            def _read_total(self):
                total = jnp.sum(self._staged)
                return np.asarray(total)
        """)
    sf = _by_checker(new, "sync")
    assert any("gauge-lambda:float()" in f.symbol for f in sf), \
        [f.render() for f in new]
    assert any("_read_total" in f.symbol and "asarray" in f.symbol
               for f in sf), [f.render() for f in new]
    assert not any("queue_depth" in f.render() for f in sf)


def test_sync_metadata_reads_never_flag(tmp_path):
    """.nbytes/.shape/.dtype on a device array are host-side metadata —
    reading them never syncs, even under a lock."""
    new = _lint(tmp_path, """\
        import threading

        import jax.numpy as jnp


        class Meter:
            def __init__(self):
                self._lock = threading.Lock()
                self.bytes = 0

            def measure(self, x):
                dev = jnp.sum(x)
                with self._lock:
                    self.bytes += int(dev.nbytes)
        """)
    assert not _by_checker(new, "sync"), [f.render() for f in new]


# --------------------------------------------------------------------------
# HBM accounting conservation (dataflow tier)
# --------------------------------------------------------------------------

CONSERVATION_PRELUDE = """\
    class Manager:
        def __init__(self):
            self._entries = {}
            self._staged_bytes = 0

        def _account(self):
            self._staged_bytes += 1

        def _release_all(self, doomed):
            for r in doomed:
                r.release()

        def get(self, name):
            e = self._entries.get(name)
            if e is not None:
                return e.resident
            return None

"""


def test_conservation_catches_unreleased_pop(tmp_path):
    new = _lint(tmp_path, CONSERVATION_PRELUDE + """\
        def evict(self, name):
            e = self._entries.pop(name, None)
            if e is not None:
                self._staged_bytes -= 1  # accounted, but never released
""")
    cf = _by_checker(new, "conservation")
    assert any("evict" in f.symbol and f.symbol.endswith("remove")
               for f in cf), [f.render() for f in new]


def test_conservation_release_on_exception_edge(tmp_path):
    """A release only on the try fall-through leaks on the handler path —
    the exception-edged CFG must see it; releasing in a ``finally``
    satisfies every path."""
    new = _lint(tmp_path, CONSERVATION_PRELUDE + """\
        def evict_leaky(self, name):
            e = self._entries.pop(name, None)
            if e is None:
                return
            try:
                self._prepare(e)
            except ValueError:
                return  # handler path: e.resident leaks until GC
            e.resident.release()

        def evict_safe(self, name):
            e = self._entries.pop(name, None)
            if e is None:
                return
            try:
                self._prepare(e)
            finally:
                e.resident.release()
""")
    cf = _by_checker(new, "conservation")
    assert any("evict_leaky" in f.symbol for f in cf), \
        [f.render() for f in new]
    assert not any("evict_safe" in f.symbol for f in cf)


def test_conservation_catches_unaccounted_insert(tmp_path):
    new = _lint(tmp_path, CONSERVATION_PRELUDE + """\
        def put(self, name, r):
            self._entries[name] = r  # stagedBytes never re-measured

        def put_ok(self, name, r):
            self._entries[name] = r
            self._account()
""")
    cf = _by_checker(new, "conservation")
    assert any("put" in f.symbol and f.symbol.endswith("insert")
               for f in cf), [f.render() for f in new]
    assert not any("put_ok" in f.symbol for f in cf)


def test_conservation_cache_parity_star_tree_nodes(tmp_path):
    """Star-tree node-array residents obey the same byte-accounting and
    release obligations as column residents: a node cache populated via
    ``setdefault`` (no plain subscript assignment anywhere) that nbytes()
    cannot see and release() never drops must be flagged on both axes."""
    new = _lint(tmp_path, """\
        class StagedNodes:
            def __init__(self):
                self._columns = {}
                self._startree = {}

            def column(self, name):
                col = object()
                self._columns[name] = col
                return col

            def startree_nodes(self, i):
                return self._startree.setdefault(i, {"dim": object()})

            def nbytes(self):
                return len(self._columns)

            def release(self):
                self._columns.clear()
        """)
    cf = _by_checker(new, "conservation")
    assert any("_startree" in f.symbol and f.symbol.endswith("nbytes")
               for f in cf), [f.render() for f in new]
    assert any("_startree" in f.symbol and f.symbol.endswith("release")
               for f in cf), [f.render() for f in new]
    assert not any("_columns" in f.symbol for f in cf)


def test_conservation_chunkacct_store_must_reach_counter(tmp_path):
    """PR 17 mutable-staging obligation: every store into a
    ``self.*chunk*`` collection must reach the class's byte counter on
    EVERY path out of the method — an early return that skips the
    recount, or a method with no recount at all, grows the device image
    invisibly to the HBM budget."""
    new = _lint(tmp_path, """\
        class StagedChunks:
            def __init__(self):
                self._chunks = {}
                self._staged_bytes = 0

            def _recount(self):
                total = 0
                for a in self._chunks.values():
                    total += a
                self._staged_bytes = total

            def install_ok(self, key, arr):
                self._chunks[key] = arr
                self._recount()

            def install_bad(self, key, arr):
                self._chunks[key] = arr

            def install_branchy(self, key, arr, cond):
                self._chunks[key] = arr
                if cond:
                    return
                self._recount()

            def nbytes(self):
                total = 0
                for a in self._chunks.values():
                    total += a
                return max(total, self._staged_bytes)

            def release(self):
                self._chunks.clear()
                self._staged_bytes = 0
        """)
    cf = _by_checker(new, "conservation")
    assert any(f.symbol == "StagedChunks.install_bad:chunkacct"
               for f in cf), [f.render() for f in new]
    assert any(f.symbol == "StagedChunks.install_branchy:chunkacct"
               for f in cf), [f.render() for f in new]
    assert not any("install_ok" in f.symbol for f in cf), \
        [f.render() for f in cf]


def test_conservation_chunkacct_no_accounting_method_at_all(tmp_path):
    """A chunk-storing resident with nbytes()/release() but NO byte
    counter anywhere cannot discharge the obligation — every store is a
    finding (the counter is what residency accounting re-measures)."""
    new = _lint(tmp_path, """\
        class NoCounter:
            def __init__(self):
                self._chunks = {}

            def put(self, key, arr):
                self._chunks[key] = arr

            def nbytes(self):
                return len(self._chunks)

            def release(self):
                self._chunks.clear()
        """)
    cf = _by_checker(new, "conservation")
    assert any(f.symbol == "NoCounter.put:chunkacct"
               and "no byte-counter" in f.message
               for f in cf), [f.render() for f in new]


def test_conservation_idxacct_pin_must_reach_accounting(tmp_path):
    """PR 18 index-rung obligation: a ``.index_slice(...)`` call pins a
    freshly-built device idx array on a staged resident, so every
    fall-through path out of the function must reach a residency
    ``.account(...)`` call (or a direct ``*bytes*`` counter write) — a
    branch that returns early leaves the budget's running view predating
    the pinned slice. Exception paths are exempt (nbytes() walks the
    slice cache; the next refresh re-measures)."""
    new = _lint(tmp_path, """\
        def serve_ok(executor, staged, key, build, name, lease):
            idx = staged.index_slice(key, build)
            executor.residency.account(name, lease)
            return idx

        def serve_bad(executor, staged, key, build, name, lease):
            idx = staged.index_slice(key, build)
            return idx

        def serve_branchy(executor, staged, key, build, name, lease, hot):
            idx = staged.index_slice(key, build)
            if hot:
                return idx
            executor.residency.account(name, lease)
            return idx

        def serve_exc_ok(executor, staged, key, build, name, lease):
            try:
                idx = staged.index_slice(key, build)
                executor.residency.account(name, lease)
            except Exception:
                return None
            return idx
        """)
    cf = _by_checker(new, "conservation")
    assert any(f.symbol == "serve_bad:idxacct"
               for f in cf), [f.render() for f in new]
    assert any(f.symbol == "serve_branchy:idxacct"
               for f in cf), [f.render() for f in new]
    assert not any("serve_ok" in f.symbol for f in cf), \
        [f.render() for f in cf]
    assert not any("serve_exc_ok" in f.symbol for f in cf), \
        [f.render() for f in cf]


def test_conservation_catches_discarded_pop(tmp_path):
    new = _lint(tmp_path, CONSERVATION_PRELUDE + """\
        def drop(self, name):
            self._entries.pop(name, None)
""")
    cf = _by_checker(new, "conservation")
    assert any("drop" in f.symbol and "discard" in f.symbol
               for f in cf), [f.render() for f in new]


HOST_TIER_PRELUDE = """\
    class TieredManager:
        def __init__(self):
            self._entries = {}
            self._host_entries = {}
            self._staged_bytes = 0
            self._host_bytes = 0

        def _release_all(self, doomed):
            for r in doomed:
                r.release()

        def _release_host(self, e):
            self._host_bytes -= e.nbytes

        def get(self, name):
            e = self._entries.get(name)
            if e is not None:
                return e.resident
            return None

        def get_host(self, name):
            e = self._host_entries.get(name)
            if e is not None:
                return e.resident
            return None

"""


def test_conservation_host_tier_demote_without_account(tmp_path):
    """The host-tier half of the byte-accounting conservation family: a
    demotion that inserts the image into the host dict WITHOUT adjusting
    host bytes lets the running total drift from reality — the insert
    rule must extend to the host tier unchanged."""
    new = _lint(tmp_path, HOST_TIER_PRELUDE + """\
        def demote_bad(self, name, image):
            e = self._entries.pop(name, None)
            if e is not None:
                self._release_all([e.resident])
                self._host_entries[name] = image  # bytes never accounted

        def demote_ok(self, name, image):
            e = self._entries.pop(name, None)
            if e is not None:
                self._release_all([e.resident])
                self._host_entries[name] = image
                self._host_bytes += image.nbytes
""")
    cf = _by_checker(new, "conservation")
    assert any("demote_bad" in f.symbol and f.symbol.endswith("insert")
               for f in cf), [f.render() for f in new]
    assert not any("demote_ok" in f.symbol for f in cf)


def test_conservation_host_tier_pop_must_account(tmp_path):
    """Host-tier removal -> accounting (the new ``hostacct`` obligation):
    the host total is a RUNNING counter, so a promotion that pops an
    image and even releases it — but never subtracts its bytes — drifts
    the host budget forever. Accounting only on the try fall-through
    leaks on the handler path (exception edges included)."""
    new = _lint(tmp_path, HOST_TIER_PRELUDE + """\
        def promote_bad(self, name):
            he = self._host_entries.pop(name, None)
            if he is None:
                return None
            self._release_all([he.resident])  # released, NOT accounted
            return he.resident

        def promote_exc_leak(self, name):
            he = self._host_entries.pop(name, None)
            if he is None:
                return None
            try:
                self._validate(he)
            except ValueError:
                self._release_all([he.resident])
                return None  # handler path skips the accounting
            self._release_host(he)
            return he.resident

        def promote_ok(self, name):
            he = self._host_entries.pop(name, None)
            if he is None:
                return None
            self._release_host(he)
            return he.resident
""")
    cf = _by_checker(new, "conservation")
    assert any("promote_bad" in f.symbol and "hostacct" in f.symbol
               for f in cf), [f.render() for f in new]
    assert any("promote_exc_leak" in f.symbol and "hostacct" in f.symbol
               for f in cf), [f.render() for f in new]
    assert not any("promote_ok" in f.symbol and "hostacct" in f.symbol
                   for f in cf), [f.render() for f in new]


def test_conservation_spanpair_open_without_close(tmp_path):
    """The spanpair obligation: a span_begin assigned to a local must
    reach a span_end naming it on every path — an open that never closes
    corrupts the query's trace tree. With-statement spans and
    returned/stored spans create no obligation."""
    new = _lint(tmp_path, """\
        def open_no_close(rec):
            sp = rec.span_begin("x")
            do_work(sp)

        def open_ok_finally(rec):
            sp = rec.span_begin("x")
            try:
                do_work(sp)
            finally:
                rec.span_end(sp)

        def open_ok_with(rec):
            with rec.span("x"):
                do_work()

        def open_ok_returned(rec):
            sp = rec.span_begin("x")
            return sp

        def open_ok_stored(rec, stats):
            sp = rec.span_begin("x")
            stats._root_span = sp

        def open_ok_closure(rec):
            sp = rec.span_begin("x")

            def done(result):
                rec.span_end(sp)
                return result

            return done

        def discarded(rec):
            rec.span_begin("x")
            do_work()
""")
    cf = _by_checker(new, "conservation")
    assert any("open_no_close" in f.symbol and "spanpair" in f.symbol
               for f in cf), [f.render() for f in new]
    assert any("discarded" in f.symbol and "spanpair-discard" in f.symbol
               for f in cf), [f.render() for f in new]
    for ok in ("open_ok_finally", "open_ok_with", "open_ok_returned",
               "open_ok_stored", "open_ok_closure"):
        assert not any(ok in f.symbol for f in cf), \
            [f.render() for f in cf]


def test_conservation_spanpair_exception_edge(tmp_path):
    """A span_end that lives only on the try fall-through leaks the span
    on the handler path — exception edges are part of the obligation."""
    new = _lint(tmp_path, """\
        def exc_leak(rec):
            sp = rec.span_begin("x")
            try:
                do_work()
            except ValueError:
                return None
            rec.span_end(sp)

        def exc_ok(rec):
            sp = rec.span_begin("x")
            try:
                do_work()
            except ValueError:
                rec.span_end(sp)
                return None
            rec.span_end(sp)

        def none_guard_ok(rec, traced):
            sp = rec.span_begin("x") if traced else None
            try:
                do_work()
            finally:
                if sp is not None:
                    rec.span_end(sp)
""")
    cf = _by_checker(new, "conservation")
    assert any("exc_leak" in f.symbol and "spanpair" in f.symbol
               for f in cf), [f.render() for f in new]
    assert not any("exc_ok" in f.symbol for f in cf), \
        [f.render() for f in cf]
    assert not any("none_guard_ok" in f.symbol for f in cf), \
        [f.render() for f in cf]


# --------------------------------------------------------------------------
# CLI: --json / --families
# --------------------------------------------------------------------------

BAD_LOCK_SRC = """\
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._d = {}  # guarded-by: _lock

        def peek(self):
            return self._d.get("k")
    """


def test_cli_json_output(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(BAD_LOCK_SRC))
    rc = lint_main([str(bad), "--json"])
    out = capsys.readouterr().out
    rows = [json.loads(line) for line in out.splitlines()]
    assert rc == 1 and rows
    assert set(rows[0]) == {"key", "family", "file", "line", "message"}
    assert rows[0]["family"] == "lock-guard"


def test_cli_families_filter(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(BAD_LOCK_SRC))
    # the finding is lock-guard: a protocol-only run must not see it
    assert lint_main([str(bad), "--families", "protocol,sync"]) == 0
    assert lint_main([str(bad), "--families", "lock-guard"]) == 1
    assert lint_main([str(bad), "--families", "nonsense"]) == 2


# --------------------------------------------------------------------------
# decline family: pallas decline-reason drift (engine/pallas_kernels.py
# strings must resolve to registered ledger codes)
# --------------------------------------------------------------------------

def test_decline_catches_unclassifiable_ineligible(tmp_path):
    """A NEW _Ineligible message with no classify_decline rule would mint
    an ad-hoc sanitized code on the ledger — flagged at lint time."""
    new = _lint(tmp_path, """\
        class _Ineligible(Exception):
            pass

        def extract(plan):
            raise _Ineligible("some brand new unlisted obstacle")
        """, name="pallas_kernels.py")
    found = _by_checker(new, "decline")
    assert len(found) == 1
    assert "classify_decline" in found[0].message


def test_decline_catches_unregistered_code(tmp_path):
    """decline('...') literals are direct ledger codes: they must appear
    in tracing.DIRECT_DECLINE_CODES (or the rules table)."""
    new = _lint(tmp_path, """\
        def bind(decline):
            decline("pallas_brand_new_unregistered_code")
        """, name="pallas_kernels.py")
    found = _by_checker(new, "decline")
    assert len(found) == 1
    assert "DIRECT_DECLINE_CODES" in found[0].message


def test_decline_known_strings_are_clean(tmp_path):
    """Registered codes and classifiable messages pass; dynamic args are
    exempt (runtime namespacing covers them)."""
    new = _lint(tmp_path, """\
        class _Ineligible(Exception):
            pass

        def extract(plan, decline, op):
            decline("pallas_too_many_groups")
            if plan:
                raise _Ineligible("lut with too many runs")
            raise _Ineligible(op)   # dynamic: exempt
        """, name="pallas_kernels.py")
    assert not _by_checker(new, "decline")


def test_decline_only_scopes_pallas_kernels_module(tmp_path):
    """Other modules calling something named decline() are out of scope."""
    new = _lint(tmp_path, """\
        def f(decline):
            decline("not_a_pallas_code_at_all")
        """, name="other_module.py")
    assert not _by_checker(new, "decline")


# --------------------------------------------------------------------------
# device family (ISSUE 15): TPU-lowering obligations on the kernel
# builders — each acceptance mutation is a scratch copy of the REAL
# module with one seeded violation, and must yield exactly one finding
# --------------------------------------------------------------------------

def _real_src(rel):
    with open(os.path.join(PKG, *rel.split("/")), encoding="utf-8") as f:
        return f.read()


def _device_scratch(tmp_path, name, src):
    p = tmp_path / name
    p.write_text(src)
    new, _ = run_lint([str(p)])
    return _by_checker(new, "device")


def test_device_clean_on_real_builders(tmp_path):
    for rel, name in (("engine/pallas_kernels.py", "pallas_kernels.py"),
                      ("parallel/combine.py", "combine.py"),
                      ("parallel/reduce_device.py", "reduce_device.py"),
                      ("engine/plan.py", "plan.py"),
                      ("engine/startree_device.py", "startree_device.py")):
        hits = _device_scratch(tmp_path, name, _real_src(rel))
        assert not hits, (rel, [f.render() for f in hits])


def test_device_reduce_bad_axis_through_helper_param(tmp_path):
    """PR-16 seeded mutations: a literal axis at the dense-rung combine
    dispatch that is NOT the declared ``MERGE_AXIS`` — resolved
    interprocedurally through the helper's ``axis`` param (one mutation
    per combine flavor: the psum helper and the all_to_all helper),
    exactly one finding each."""
    src = _real_src("parallel/reduce_device.py")
    for target in ('_axis_reduce(v, op, MERGE_AXIS, mesh)',
                   '_slice_reduce(v, op, MERGE_AXIS, mesh)'):
        bad = src.replace(target, target.replace("MERGE_AXIS", '"rows"'))
        assert bad != src, \
            f"dense-rung combine dispatch moved ({target}); update fixture"
        hits = _device_scratch(tmp_path, "reduce_device.py", bad)
        assert len(hits) == 1 \
            and "not a declared mesh axis" in hits[0].message, \
            (target, [f.render() for f in hits])


def test_device_swapped_blockspec_dim(tmp_path):
    """Seeded mutation 1: a swapped BlockSpec dim — the lane (last) dim
    is no longer provably %128."""
    src = _real_src("engine/pallas_kernels.py")
    bad = src.replace(
        "pl.BlockSpec((Mm, G), lambda s, t: (0, 0), "
        "memory_space=pltpu.VMEM),",
        "pl.BlockSpec((G, Mm), lambda s, t: (0, 0), "
        "memory_space=pltpu.VMEM),")
    assert bad != src, "out-spec line moved; update the fixture"
    hits = _device_scratch(tmp_path, "pallas_kernels.py", bad)
    assert len(hits) == 1 and "lane dim" in hits[0].message, \
        [f.render() for f in hits]


def test_device_unpadded_group_count_through_helper(tmp_path):
    """Seeded mutation: ``padded_groups`` (whose return reaches
    ``num_groups_padded`` in extract_plan) stops rounding up to whole
    128-lane chunks — the gpad rule follows the call into the helper."""
    src = _real_src("engine/pallas_kernels.py")
    bad = src.replace(
        "    return -(-num_groups // _G_CHUNK) * _G_CHUNK\n",
        "    return num_groups\n")
    assert bad != src, "padded_groups moved; update the fixture"
    hits = _device_scratch(tmp_path, "pallas_kernels.py", bad)
    assert len(hits) == 1 and "lane-padded" in hits[0].message, \
        [f.render() for f in hits]


def test_device_helper_concat_swap_flags_every_call_shape(tmp_path):
    """The block() helper's (1, 1) prefix swapped to a suffix puts a
    size-1 lane dim on every helper-built block — one finding per
    distinct call-site shape."""
    src = _real_src("engine/pallas_kernels.py")
    bad = src.replace("return pl.BlockSpec((1, 1) + shape0,",
                      "return pl.BlockSpec(shape0 + (1, 1),")
    assert bad != src
    hits = _device_scratch(tmp_path, "pallas_kernels.py", bad)
    assert len(hits) == 2, [f.render() for f in hits]
    assert all("lane dim" in f.message for f in hits)


def test_device_over_cap_ivs_lut(tmp_path):
    """Seeded mutation 2: an over-cap ivs LUT — the module's run cap
    outgrowing the pallas.lut.max.runs config table."""
    src = _real_src("engine/pallas_kernels.py")
    bad = src.replace("DEFAULT_LUT_RUN_CAP = 64",
                      "DEFAULT_LUT_RUN_CAP = 1024")
    assert bad != src
    hits = _device_scratch(tmp_path, "pallas_kernels.py", bad)
    assert len(hits) == 1 and "DEFAULT_PALLAS_LUT_MAX_RUNS" \
        in hits[0].message, [f.render() for f in hits]


def test_device_i64_inside_kernel_body(tmp_path):
    """Seeded mutation 3: an i64 op outside the blessed limb-reassembly
    pattern — here, inside the kernel body itself."""
    src = _real_src("engine/pallas_kernels.py")
    bad = src.replace("m_i = mask.astype(jnp.int32)",
                      "m_i = mask.astype(jnp.int64)")
    assert bad != src
    hits = _device_scratch(tmp_path, "pallas_kernels.py", bad)
    assert len(hits) == 1 and "Pallas kernel body" in hits[0].message, \
        [f.render() for f in hits]


def test_device_i64_outside_blessed_functions(tmp_path):
    src = _real_src("engine/pallas_kernels.py")
    bad = src.replace(
        "def _segment_args(pp: PallasPlan, staged: StagedSegment):\n",
        "def _segment_args(pp: PallasPlan, staged: StagedSegment):\n"
        "    _w = jnp.int64(0)\n")
    assert bad != src
    hits = _device_scratch(tmp_path, "pallas_kernels.py", bad)
    assert len(hits) == 1 and "blessed" in hits[0].message, \
        [f.render() for f in hits]


def test_device_mismatched_psum_axis(tmp_path):
    """Seeded mutation 4: a psum over an axis name the mesh never
    declared."""
    src = _real_src("parallel/combine.py")
    bad = src.replace('local = jax.lax.psum(local, DOC_AXIS)',
                      'local = jax.lax.psum(local, "docs")')
    assert bad != src
    hits = _device_scratch(tmp_path, "combine.py", bad)
    assert len(hits) == 1 and "'docs'" in hits[0].message, \
        [f.render() for f in hits]


def test_device_bad_axis_through_helper_param(tmp_path):
    """Interprocedural: a bad literal handed to _cross_reduce's axes
    param is flagged at the call site."""
    src = _real_src("parallel/combine.py")
    bad = src.replace(
        'seg_local = _cross_reduce(seg_local, "sum", (DOC_AXIS,), mesh)',
        'seg_local = _cross_reduce(seg_local, "sum", ("docs",), mesh)')
    assert bad != src
    hits = _device_scratch(tmp_path, "combine.py", bad)
    assert len(hits) == 1, [f.render() for f in hits]


def test_device_value_ref_count_drift(tmp_path):
    """value_limbs planes must size the ref blocks: a value-spec loop
    counting inputs instead of planes is the i64 read-someone-else's-
    plane bug."""
    src = _real_src("engine/pallas_kernels.py")
    bad = src.replace(
        "for _ in range(n_value_refs):\n        "
        "in_specs.append(block((RT, 128)))",
        "for _ in range(n_values):\n        "
        "in_specs.append(block((RT, 128)))")
    assert bad != src
    hits = _device_scratch(tmp_path, "pallas_kernels.py", bad)
    assert len(hits) == 1 and "value_limbs" in hits[0].message, \
        [f.render() for f in hits]


def test_device_narrow_drops_pow2(tmp_path):
    src = _real_src("engine/plan.py")
    bad = src.replace("    num_groups = _next_pow2(total)",
                      "    num_groups = total")
    assert bad != src
    hits = _device_scratch(tmp_path, "plan.py", bad)
    assert len(hits) == 1 and "_next_pow2" in hits[0].message, \
        [f.render() for f in hits]


def test_device_narrow_drops_capacity(tmp_path):
    src = _real_src("engine/plan.py")
    bad = src.replace(
        "    spec = (filter_spec, agg_specs, group_specs, num_groups, "
        "capacity)",
        "    spec = (filter_spec, agg_specs, group_specs, num_groups, "
        "4096)")
    assert bad != src
    hits = _device_scratch(tmp_path, "plan.py", bad)
    assert len(hits) == 1 and "capacity" in hits[0].message, \
        [f.render() for f in hits]


def test_device_startree_idx_pad_off_spec(tmp_path):
    src = _real_src("engine/startree_device.py")
    bad = src.replace("padded = np.zeros(capacity, dtype=np.int32)",
                      "padded = np.zeros(n, dtype=np.int32)")
    assert bad != src
    hits = _device_scratch(tmp_path, "startree_device.py", bad)
    assert len(hits) == 1 and "capacity" in hits[0].message, \
        [f.render() for f in hits]


# --------------------------------------------------------------------------
# --changed mode + the wall-clock budget
# --------------------------------------------------------------------------

def _git(cwd, *args):
    import subprocess

    subprocess.run(["git", *args], cwd=cwd, check=True,
                   capture_output=True,
                   env={**os.environ,
                        "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
                        "GIT_COMMITTER_NAME": "t",
                        "GIT_COMMITTER_EMAIL": "t@t"})


def test_changed_selects_reverse_and_forward_deps(tmp_path):
    """--changed lints the changed file, its reverse importers
    (transitively), and one forward hop of context for every selected
    file — not the whole tree."""
    from pinot_tpu.tools.lint.core import select_changed

    pkg = tmp_path / "mypkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "base.py").write_text("X = 1\n")
    (pkg / "mid.py").write_text("from mypkg.base import X\nY = X\n")
    (pkg / "top.py").write_text("from mypkg import mid\nZ = mid.Y\n")
    (pkg / "island.py").write_text("W = 9\n")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", ".")
    _git(tmp_path, "commit", "-qm", "seed")
    (pkg / "mid.py").write_text("from mypkg.base import X\nY = X + 1\n")
    got = {os.path.basename(p)
           for p in select_changed("HEAD", str(pkg))}
    # mid changed; top imports mid (reverse, transitive); base is mid's
    # forward context (and __init__ is top's); island untouched
    assert got == {"mid.py", "top.py", "base.py", "__init__.py"}

    _git(tmp_path, "add", ".")
    _git(tmp_path, "commit", "-qm", "mid")
    assert select_changed("HEAD", str(pkg)) == []


def test_changed_cli_on_this_repo():
    """The CLI path end-to-end against the real repo: HEAD-relative
    selection runs and stays zero-finding (same gate as the package)."""
    assert lint_main(["--changed", "HEAD"]) == 0


def test_whole_package_wall_clock_budget():
    """The whole-package run must stay CI-viable as the dataflow tier
    grows — v4 added three more families (decisions totality over the
    ledger scope CFGs, the exactness proof guards, config-key
    conformance with the README table check) and v5 adds the whole-
    program thread-topology family, paid for by the shared parse/CFG
    tier (one ast.parse + one CFG per function, reused by all 14
    families): a generous multiple of the measured wall clock, but a
    hard ceiling — a quadratic blow-up in a new family fails here
    before it fails the CI budget."""
    import time

    t0 = time.perf_counter()
    run_lint([PKG], baseline=DEFAULT_BASELINE)
    elapsed = time.perf_counter() - t0
    assert elapsed < 120, f"whole-package lint took {elapsed:.1f}s"


# --------------------------------------------------------------------------
# v4: decision-path totality (seeded mutations, each exactly one finding)
# --------------------------------------------------------------------------

def _lint_family(tmp_path, source, family, name="fixture.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    new, _accepted = run_lint([str(p)], families=[family])
    return new


def test_decisions_dropped_record_on_return_path(tmp_path):
    """A scoped rung probe with one decline exit that never reaches the
    ledger: exactly the silent-fallback shape the family exists for."""
    new = _lint_family(tmp_path, """\
        def record_decision(stats, point, chosen, declined, reason):
            pass

        def _try_star_tree(self, ctx, aggs, seg, stats):
            tree = seg.tree
            if tree is None:
                return None
            record_decision(stats, "startree", "scan", "startree", "tree1")
            return None
        """, "decisions", name="executor.py")
    assert len(new) == 1
    assert new[0].checker == "decisions"
    assert "_try_star_tree" in new[0].symbol


def test_decisions_dropped_record_on_exception_edge(tmp_path):
    """A handler that swallows the rung's failure and returns None must
    record on its own — the exception edge carries the raising
    statement's PRE-state, so the record after the try doesn't count."""
    new = _lint_family(tmp_path, """\
        def record_decision(stats, point, chosen, declined, reason):
            pass

        def _try_star_tree(self, ctx, aggs, seg, stats):
            try:
                res = seg.walk()
            except ValueError:
                return None
            record_decision(stats, "startree", "scan", "startree", "tree1")
            return res
        """, "decisions", name="executor.py")
    assert len(new) == 1
    assert "exit" in new[0].symbol


def test_decisions_discharges_are_clean(tmp_path):
    """The three legitimate unrecorded-exit shapes: the 'not a decline'
    annotation, the hook-credited pass-through (x = f(on_decline=...)
    then `if x is None: return None`), and the vacuous-hook guard."""
    new = _lint_family(tmp_path, """\
        def record_decision(stats, point, chosen, declined, reason):
            pass

        def _try_star_tree(self, ctx, aggs, seg, stats, on_decline=None):
            if seg is None:
                return None  # no segment shipped: not a decline
            pick = self._pick(seg, on_decline=on_decline)
            if pick is None:
                return None
            if on_decline is None:
                return None
            record_decision(stats, "startree", "scan", "startree", "tree1")
            return None
        """, "decisions", name="executor.py")
    assert not new, [f.render() for f in new]


def test_decisions_all_mode_checks_every_exit(tmp_path):
    """`all`-mode scope (routing pruners, the hybrid split): a non-None
    return without a record is a finding too."""
    new = _lint_family(tmp_path, """\
        def record_decision(stats, point, chosen, declined, reason):
            pass

        def _time_prune(self, ctx, segments):
            if not segments:
                return segments
            record_decision(None, "routing", "pruned", "all_servers",
                            "time_prune")
            return [s for s in segments if s.live]
        """, "decisions", name="routing.py")
    assert len(new) == 1
    assert "_time_prune" in new[0].symbol


def test_decisions_unregistered_reason_literal(tmp_path):
    """Every literal reason at a scoped recorder call must discharge
    against tracing.reason_registry()."""
    new = _lint_family(tmp_path, """\
        def record_decision(stats, point, chosen, declined, reason):
            pass

        def _try_star_tree(self, ctx):
            record_decision(None, "startree", "scan", "startree",
                            "totally_bogus_reason")
            return 1
        """, "decisions", name="executor.py")
    assert len(new) == 1
    assert "totally_bogus_reason" in new[0].symbol


# --------------------------------------------------------------------------
# v4: numeric-exactness proof guards
# --------------------------------------------------------------------------

def test_exactness_raw_wide_literal(tmp_path):
    new = _lint_family(tmp_path, """\
        def fold_cap(n):
            return n < 1 << 62
        """, "exactness")
    assert len(new) == 1
    assert "wide_literal" in new[0].symbol


def test_exactness_power_form_also_banned(tmp_path):
    new = _lint_family(tmp_path, """\
        LIMIT = 2 ** 53
        """, "exactness")
    assert len(new) == 1


def test_exactness_dtype_mismatched_guard(tmp_path):
    """Comparing a float path against an i64 bound proves nothing: no
    integer-dtype evidence anywhere in the function."""
    new = _lint_family(tmp_path, """\
        from pinot_tpu.common.bounds import I64_FOLD_BOUND

        def check(arr):
            total = arr.sum() * 2.5
            return total < I64_FOLD_BOUND
        """, "exactness")
    assert len(new) == 1
    assert "i64_evidence" in new[0].symbol


def test_exactness_guard_deletion_is_a_finding(tmp_path):
    """The known sum-reassembly sites must keep a bounds-constant guard
    even after every raw literal is gone."""
    new = _lint_family(tmp_path, """\
        def _finish_group_by(self):
            return self._rows
        """, "exactness", name="reduce.py")
    assert len(new) == 1
    assert "guard_missing" in new[0].symbol


def test_exactness_real_guard_shape_is_clean(tmp_path):
    new = _lint_family(tmp_path, """\
        from pinot_tpu.common.bounds import I64_FOLD_BOUND

        def _finish_group_by(self):
            if self._gb_i64_bound >= I64_FOLD_BOUND:
                return None
            return self._rows
        """, "exactness", name="reduce.py")
    assert not new, [f.render() for f in new]


# --------------------------------------------------------------------------
# v4: config-key conformance
# --------------------------------------------------------------------------

def test_configkeys_undeclared_inline_key(tmp_path):
    new = _lint_family(tmp_path, """\
        def setup(cfg):
            return cfg.get_bool("pinot.server.query.mystery.enabled",
                                False)
        """, "configkeys")
    assert len(new) == 1
    assert "pinot.server.query.mystery.enabled" in new[0].symbol


def test_configkeys_declared_keys_resolve_clean(tmp_path):
    new = _lint_family(tmp_path, """\
        from pinot_tpu.spi.config import CommonConstants

        def setup(cfg):
            return cfg.get_int(CommonConstants.RUNNER_THREADS_KEY, 8)
        """, "configkeys")
    assert not new, [f.render() for f in new]


def _configkeys_tree(tmp_path, config_src, reader_src, readme=None):
    pkg = tmp_path / "pkg"
    (pkg / "spi").mkdir(parents=True)
    (pkg / "spi" / "config.py").write_text(textwrap.dedent(config_src))
    (pkg / "reader.py").write_text(textwrap.dedent(reader_src))
    if readme is not None:
        (tmp_path / "README.md").write_text(textwrap.dedent(readme))
    new, _ = run_lint([str(pkg)], families=["configkeys"])
    return new


def test_configkeys_declared_but_unread_key(tmp_path):
    new = _configkeys_tree(tmp_path, """\
        class CommonConstants:
            USED_KEY = "pinot.server.query.used"
            GHOST_KEY = "pinot.server.query.ghost"
        """, """\
        from pkg.spi.config import CommonConstants

        def setup(cfg):
            return cfg.get(CommonConstants.USED_KEY, None)
        """)
    assert len(new) == 1
    assert "unread:GHOST_KEY" in new[0].symbol


def test_configkeys_stale_readme_default(tmp_path):
    new = _configkeys_tree(tmp_path, """\
        class CommonConstants:
            RUNNER_THREADS_KEY = "pinot.server.query.runner.threads"
            DEFAULT_RUNNER_THREADS = 8
        """, """\
        from pkg.spi.config import CommonConstants

        def setup(cfg):
            return cfg.get_int(CommonConstants.RUNNER_THREADS_KEY, 8)
        """, readme="""\
        # fixture

        <!-- config-keys:begin -->
        | key | default | controls |
        |---|---|---|
        | `pinot.server.query.runner.threads` | `4` | runner pool |
        <!-- config-keys:end -->
        """)
    assert len(new) == 1
    assert "readme:stale:RUNNER_THREADS_KEY" in new[0].symbol


def test_configkeys_readme_row_matching_code_is_clean(tmp_path):
    new = _configkeys_tree(tmp_path, """\
        class CommonConstants:
            RUNNER_THREADS_KEY = "pinot.server.query.runner.threads"
            DEFAULT_RUNNER_THREADS = 8
        """, """\
        from pkg.spi.config import CommonConstants

        def setup(cfg):
            return cfg.get_int(CommonConstants.RUNNER_THREADS_KEY, 8)
        """, readme="""\
        # fixture

        <!-- config-keys:begin -->
        | key | default | controls |
        |---|---|---|
        | `pinot.server.query.runner.threads` | `8` | runner pool |
        <!-- config-keys:end -->
        """)
    assert not new, [f.render() for f in new]


def test_cli_sarif_output(tmp_path, capsys):
    """--sarif: one SARIF 2.1.0 run, one rule per family, results carry
    the stable baseline key as a partial fingerprint."""
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""\
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._d = {}  # guarded-by: _lock

            def peek(self):
                return self._d.get("k")
        """))
    assert lint_main([str(bad), "--sarif", "--no-baseline"]) == 1
    log = json.loads(capsys.readouterr().out)
    assert log["version"] == "2.1.0"
    run = log["runs"][0]
    assert run["tool"]["driver"]["name"] == "graftlint"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"decisions", "exactness", "configkeys", "threads"} <= rule_ids
    res = run["results"][0]
    assert res["ruleId"] == "lock-guard"
    assert res["locations"][0]["physicalLocation"]["region"]["startLine"]
    assert res["partialFingerprints"]["graftlintKey/v1"].startswith(
        "lock-guard:")


# --------------------------------------------------------------------------
# suppression machinery
# --------------------------------------------------------------------------

def test_inline_ignore_suppresses_with_reason(tmp_path):
    p = tmp_path / "sup.py"
    p.write_text(textwrap.dedent("""\
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._d = {}  # guarded-by: _lock

            def peek(self):
                return self._d.get("k")  # lint: ignore[lock-guard] — stats-only racy read
        """))
    new, accepted = run_lint([str(p)])
    assert not new
    assert len(accepted) == 1


def test_baseline_suppresses_by_stable_key(tmp_path):
    src = textwrap.dedent("""\
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._d = {}  # guarded-by: _lock

            def peek(self):
                return self._d.get("k")
        """)
    p = tmp_path / "base.py"
    p.write_text(src)
    new, _ = run_lint([str(p)])
    assert len(new) == 1
    bl = tmp_path / "baseline.json"
    bl.write_text('{"entries": [{"key": "%s", "reason": "test"}]}'
                  % new[0].key)
    new2, accepted2 = run_lint([str(p)], baseline=str(bl))
    assert not new2 and len(accepted2) == 1


# --------------------------------------------------------------------------
# v5: thread-topology race analysis (seeded mutations, each exactly one
# finding; the real modules stay clean under the same rules)
# --------------------------------------------------------------------------

def test_threads_unguarded_cross_role_write(tmp_path):
    """A daemon sampler thread writing a field the request path reads,
    with no lock anywhere: the core race the family exists for."""
    new = _lint_family(tmp_path, """\
        import threading

        class Sampler:
            def __init__(self):
                self.ticks = 0
                self._thread = None

            def start(self):
                self._thread = threading.Thread(
                    target=self._loop, name="telemetry-sampler-0",
                    daemon=True)
                self._thread.start()

            def _loop(self):
                self.ticks += 1

            def snapshot(self):
                return self.ticks
        """, "threads")
    assert len(new) == 1, [f.render() for f in new]
    assert "Sampler.ticks" in new[0].key
    assert "sampler" in new[0].message and "request" in new[0].message


def test_threads_role_widened_by_new_submit_site(tmp_path):
    """A worker confined to the prefetch thread is clean; adding ONE
    ``pool.submit`` call from the public surface widens its role set and
    the previously-confined field becomes a finding."""
    confined = """\
        import threading

        class Prefetcher:
            def __init__(self):
                self.staged = 0
                self._thread = None

            def start(self):
                self._thread = threading.Thread(
                    target=self._drain, name="hbm-prefetch-0", daemon=True)
                self._thread.start()

            def _drain(self):
                self.staged += 1
        """
    assert _lint_family(tmp_path, confined, "threads") == []
    new = _lint_family(tmp_path, confined + """\

            def flush(self, pool):
                pool.submit(self._drain)
        """, "threads", name="widened.py")
    assert len(new) == 1, [f.render() for f in new]
    assert "Prefetcher.staged" in new[0].key


def test_threads_post_spawn_write_to_immutable_field(tmp_path):
    """Publish-before-spawn: a config field written before the thread
    starts is proven immutable-after-publish; moving the write below
    ``start()`` breaks the proof and is a finding."""
    new = _lint_family(tmp_path, """\
        import threading

        class Beat:
            def __init__(self):
                self.interval = 1.0
                self._thread = None

            def boot(self, interval):
                self.interval = interval
                self._thread = threading.Thread(
                    target=self._tick, name="heartbeat-0", daemon=True)
                self._thread.start()

            def _tick(self):
                return self.interval
        """, "threads")
    assert new == [], [f.render() for f in new]
    new = _lint_family(tmp_path, """\
        import threading

        class Beat:
            def __init__(self):
                self.interval = 1.0
                self._thread = None

            def boot(self, interval):
                self._thread = threading.Thread(
                    target=self._tick, name="heartbeat-0", daemon=True)
                self._thread.start()
                self.interval = interval

            def _tick(self):
                return self.interval
        """, "threads", name="postspawn.py")
    assert len(new) == 1, [f.render() for f in new]
    assert "Beat.interval" in new[0].key


def test_threads_stale_race_ok_on_guarded_field(tmp_path):
    """A ``# race-ok:`` on a field that IS lock-guarded is a dead
    annotation — the waiver must be removed, not accumulated."""
    new = _lint_family(tmp_path, """\
        import threading

        class Guarded:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0  # guarded-by: _lock
                self._thread = None

            def start(self):
                self._thread = threading.Thread(
                    target=self._loop, name="telemetry-sampler-0",
                    daemon=True)
                self._thread.start()

            def _loop(self):
                with self._lock:
                    self.n = self.n + 1  # race-ok: single_writer

            def snapshot(self):
                with self._lock:
                    return self.n
        """, "threads")
    assert len(new) == 1, [f.render() for f in new]
    assert "Guarded.n:race-ok-dead" in new[0].key


def test_threads_race_ok_reason_must_be_registered(tmp_path):
    """A waiver only counts with a reason from
    ``tracing.RACE_OK_REASONS``; an ad-hoc reason is itself a finding,
    and a registered one silences the race."""
    racy = """\
        import threading

        class Loose:
            def __init__(self):
                self.flag = False
                self._thread = None

            def start(self):
                self._thread = threading.Thread(
                    target=self._loop, name="telemetry-sampler-0",
                    daemon=True)
                self._thread.start()

            def _loop(self):
                self.flag = True  # race-ok: %s

            def done(self):
                return self.flag
        """
    new = _lint_family(tmp_path, racy % "because_i_said_so", "threads")
    assert len(new) == 1, [f.render() for f in new]
    assert "Loose.flag:race-ok-reason" in new[0].key
    assert _lint_family(tmp_path, racy % "single_writer", "threads",
                        name="waived.py") == []


def test_threads_spawn_graph_rules(tmp_path):
    """Spawn sites carry obligations of their own: every thread needs a
    role-mapped name, and every target must resolve statically."""
    new = _lint_family(tmp_path, """\
        import threading

        def _work():
            pass

        def unnamed():
            threading.Thread(target=_work).start()

        def opaque(fn):
            threading.Thread(target=fn, name="heartbeat-0").start()
        """, "threads")
    keys = {f.key for f in new}
    assert any(k.endswith("spawn:unnamed:role") for k in keys), keys
    assert any(k.endswith("spawn:opaque:target") for k in keys), keys


def test_threads_real_modules_stay_clean():
    """The whole package under the threads family alone: every true
    positive found at landing was fixed or waived with a registered
    reason — none baselined."""
    new, _ = run_lint([PKG], families=["threads"])
    assert new == [], [f.render() for f in new]


def test_threads_changed_scope_sees_package_spawn_graph(tmp_path):
    """--changed correctness for whole-program families: the spawn graph
    is computed package-wide, findings are scoped afterwards. A spawn-
    site edit in file A surfaces the role violation in UNTOUCHED file B;
    scoping to A alone filters B's finding out; and a subset run without
    the whole-program root is blind to the package's spawn graph."""
    pkg = tmp_path / "mypkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "b.py").write_text(textwrap.dedent("""\
        class Store:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1

            def read(self):
                return self.n
        """))
    a_seed = textwrap.dedent("""\
        import threading

        from mypkg.b import Store

        STORE = Store()

        def _loop():
            STORE.bump()
        """)
    (pkg / "a.py").write_text(a_seed)
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", ".")
    _git(tmp_path, "commit", "-qm", "seed")

    # the edit: a.py gains a sampler-thread spawn site for _loop
    (pkg / "a.py").write_text(a_seed + textwrap.dedent("""\

        def start():
            threading.Thread(target=_loop, name="telemetry-sampler-0",
                             daemon=True).start()
        """))
    from pinot_tpu.tools.lint.core import select_changed

    sel = select_changed("HEAD", str(pkg))
    assert {os.path.basename(p) for p in sel} >= {"a.py", "b.py"}
    new, _ = run_lint(sel, families=["threads"],
                      whole_program_root=str(pkg))
    assert len(new) == 1, [f.render() for f in new]
    assert "Store.n" in new[0].key and new[0].path.endswith("b.py")

    # scope to a.py only: the b.py finding is out of scope
    new, _ = run_lint([str(pkg / "a.py")], families=["threads"],
                      whole_program_root=str(pkg))
    assert new == [], [f.render() for f in new]

    # no whole-program root: the subset never sees a.py's spawn site
    new, _ = run_lint([str(pkg / "b.py")], families=["threads"])
    assert new == [], [f.render() for f in new]

    # b.py alone IN scope still inherits the package spawn graph
    new, _ = run_lint([str(pkg / "b.py")], families=["threads"],
                      whole_program_root=str(pkg))
    assert len(new) == 1 and new[0].path.endswith("b.py")


# --------------------------------------------------------------------------
# v5: the shared parse/CFG tier every family reuses
# --------------------------------------------------------------------------

def test_module_cache_reuses_parses(tmp_path):
    """load_modules serves the SAME Module object for unchanged source
    (13+ families re-enter it per run) and invalidates on content — not
    mtime, which lies on fast rewrites."""
    from pinot_tpu.tools.lint.core import load_modules

    p = tmp_path / "m.py"
    p.write_text("X = 1\n")
    ctx1, _ = load_modules([str(p)])
    ctx2, _ = load_modules([str(p)])
    assert ctx1.modules[0] is ctx2.modules[0]
    p.write_text("X = 2\n")
    ctx3, _ = load_modules([str(p)])
    assert ctx3.modules[0] is not ctx2.modules[0]


def test_cfg_memo_returns_identical_graphs():
    """build_cfg memoizes per function node: the dataflow families share
    one CFG instead of rebuilding it per family."""
    import ast as _ast

    from pinot_tpu.tools.lint.dataflow import build_cfg

    fn = _ast.parse(
        "def f(x):\n    if x:\n        return 1\n    return 0\n").body[0]
    assert build_cfg(fn) is build_cfg(fn)
