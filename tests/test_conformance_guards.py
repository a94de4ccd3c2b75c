"""Regression tests for the PR-19 conformance fixes.

Each true positive the v4 graftlint families (``decisions`` /
``exactness`` / ``configkeys``) surfaced at landing time was fixed
in-code, never baselined; these tests pin the fixed behavior so a
revert re-fails loudly:

- ``common/bounds.py`` — the hoisted wide-bound constants keep their
  derivations (a typo'd bit width is exactly the bug the hoist
  prevents), and the reduce-tier guards still cut over at them;
- ``engine/executor.py`` — the host star-tree walker refusing a tree
  the pick accepted now lands in the decision ledger
  (``startree_walker_declined``) instead of silently falling to scan;
- ``broker/broker.py`` — ``device_reduce=None`` resolves through
  ``PinotConfiguration`` (``pinot.broker.reduce.device.enabled``), an
  explicit constructor argument still wins;
- ``common/telemetry.py`` — the SLO key parse is built from the
  declared ``SLO_KEY_PREFIX`` constant, so a key composed from the
  constant always parses.
"""

import numpy as np
import pandas as pd
import pytest

from pinot_tpu.common import bounds, tracing
from pinot_tpu.spi.config import CommonConstants, PinotConfiguration

pytestmark = pytest.mark.trace


class TestBounds:
    def test_values_and_derivations(self):
        assert bounds.I64_FOLD_BOUND == 2 ** 62
        assert bounds.I64_KEY_SPACE_BOUND == 2 ** 62
        assert bounds.F64_EXACT_INT_BOUND == float(2 ** 53)
        assert isinstance(bounds.F64_EXACT_INT_BOUND, float)
        assert bounds.I64_PAD_SENTINEL == 2 ** 63 - 1
        # the derivation relations the comments promise
        assert bounds.I64_FOLD_BOUND * 2 - 1 == bounds.I64_PAD_SENTINEL
        assert bounds.I64_KEY_SPACE_BOUND < bounds.I64_PAD_SENTINEL
        assert float(2 ** 53) + 1.0 == float(2 ** 53)  # why 53 is the edge
        assert float(2 ** 53 - 1) + 1.0 != float(2 ** 53 - 1)

    def test_f64_sum_exact_cuts_over_at_named_bound(self):
        from pinot_tpu.parallel.reduce_device import f64_sum_exact

        under = np.array([bounds.F64_EXACT_INT_BOUND / 2], dtype=np.float64)
        over = np.array([bounds.F64_EXACT_INT_BOUND], dtype=np.float64)
        assert f64_sum_exact(under)
        assert not f64_sum_exact(over)

    def test_composite_key_space_declines_past_named_bound(self):
        from pinot_tpu.parallel.reduce_device import encode_composite_keys

        # two i64 dims each spanning ~2^32 values: the radix product
        # exceeds I64_KEY_SPACE_BOUND, so the encoder must decline
        wide = np.array([0, 1 << 32], dtype=np.int64)
        keys, space = encode_composite_keys([wide, wide])
        assert keys is None and space == 0
        # ...while one such dim still fits
        keys, space = encode_composite_keys([wide])
        assert keys is not None and space == (1 << 32) + 1


class TestWalkerDeclineLedger:
    def test_walker_refusal_is_recorded_not_silent(self, monkeypatch,
                                                   tmp_path):
        """The pick accepts a tree, the host walker refuses it at
        execution time: the scan serves AND the ledger explains the
        fallback (the v4 `decisions` family's flagship true positive)."""
        from pinot_tpu.engine import ServerQueryExecutor, startree_exec
        from pinot_tpu.query import compile_query
        from pinot_tpu.segment import SegmentBuilder, load_segment
        from pinot_tpu.spi import DataType, FieldSpec, FieldType, Schema
        from pinot_tpu.spi.table import IndexingConfig, StarTreeIndexConfig

        rng = np.random.default_rng(7)
        n = 400
        df = pd.DataFrame({
            "country": [f"c{i}" for i in rng.integers(0, 5, n)],
            "revenue": np.round(rng.gamma(2.0, 50.0, n), 2),
        })
        schema = Schema("orders", [
            FieldSpec("country", DataType.STRING),
            FieldSpec("revenue", DataType.DOUBLE, FieldType.METRIC),
        ])
        cfg = IndexingConfig(star_tree_index_configs=[StarTreeIndexConfig(
            dimensions_split_order=["country"],
            function_column_pairs=["COUNT__*", "SUM__revenue"])])
        out = str(tmp_path)
        b = SegmentBuilder(schema, "orders_0", indexing_config=cfg)
        b.build({c: df[c].tolist() for c in df.columns}, out)
        seg = load_segment(f"{out}/orders_0")
        assert seg.metadata.star_tree_count == 1

        monkeypatch.setattr(startree_exec, "execute_with_matches",
                            lambda *a, **kw: None)
        mark = tracing.LEDGER.snapshot()
        ex = ServerQueryExecutor(use_device=False)
        table, stats = ex.execute(
            compile_query("SELECT sum(revenue) FROM orders"), [seg])
        assert table.rows[0][0] == pytest.approx(float(df["revenue"].sum()))
        delta = tracing.LEDGER.delta(mark)
        hits = [k for k in delta if "startree_walker_declined" in k]
        assert hits, f"walker refusal not in the ledger: {sorted(delta)}"
        assert "startree_walker_declined" in \
            tracing.registered_reason_codes()


class TestBrokerDeviceReduceConfig:
    def _handler(self, **kw):
        from pinot_tpu.broker.broker import BrokerRequestHandler
        from pinot_tpu.controller.state import ClusterStateStore

        return BrokerRequestHandler(ClusterStateStore(), **kw)

    def test_env_key_enables_device_reduce(self, monkeypatch):
        monkeypatch.setenv("PINOT_BROKER_REDUCE_DEVICE_ENABLED", "true")
        h = self._handler()
        try:
            assert h.reduce_service.device_reduce is True
        finally:
            h.shutdown()

    def test_default_is_declared_constant(self, monkeypatch):
        monkeypatch.delenv("PINOT_BROKER_REDUCE_DEVICE_ENABLED",
                           raising=False)
        h = self._handler()
        try:
            assert h.reduce_service.device_reduce \
                is CommonConstants.DEFAULT_BROKER_DEVICE_REDUCE
        finally:
            h.shutdown()

    def test_explicit_argument_wins_over_env(self, monkeypatch):
        monkeypatch.setenv("PINOT_BROKER_REDUCE_DEVICE_ENABLED", "true")
        h = self._handler(device_reduce=False)
        try:
            assert h.reduce_service.device_reduce is False
        finally:
            h.shutdown()


class TestSloPrefixIsDeclared:
    def test_key_built_from_constant_parses(self):
        from pinot_tpu.common.telemetry import Telemetry

        key = CommonConstants.SLO_KEY_PREFIX + "my_table_REALTIME.p99.ms"
        t = Telemetry()
        t.configure(PinotConfiguration({key: "150"}, use_env=False))
        assert t.slo.objectives()["my_table_REALTIME"]["p99_ms"] == 150.0
