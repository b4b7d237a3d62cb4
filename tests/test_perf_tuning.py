"""Unit tests for the repro.perf autotuning table (ISSUE 9).

The table's whole safety story is (a) stale tables degrade to untuned
defaults, never to wrong tiles — so every invalidation path must return
an EMPTY table, and (b) tuned values can change throughput but never
results — so validation rejects any cell that could diverge (non-pow2
blocks, unknown dtypes, int8 diagonals at L >= 127) and the env
reproducibility pin outranks the tuned dtype.
"""
import json

import jax
import jax.numpy as jnp
import pytest

from repro.perf import (
    LCSTuning, SCHEMA, TuningTable, quantize_pairs, resolve_wavefront_dtype,
    tuning_path,
)


def _table_with(key_cells):
    t = TuningTable()
    for (pairs, levels, length), tuning in key_cells.items():
        t.record(pairs, levels, length, tuning)
    return t


class TestQuantize:
    def test_ceiling_pow2(self):
        assert quantize_pairs(1) == 1
        assert quantize_pairs(2) == 2
        assert quantize_pairs(3) == 4
        assert quantize_pairs(4096) == 4096
        assert quantize_pairs(4097) == 8192

    def test_degenerate(self):
        assert quantize_pairs(0) == 1


class TestLCSTuningValidation:
    def test_rejects_unknown_dtype(self):
        with pytest.raises(ValueError, match="wavefront_dtype"):
            LCSTuning(wavefront_dtype="float32")

    def test_record_rejects_int8_at_long_lengths(self):
        # int8 diagonals saturate at 127: recording one for L >= 127 could
        # make a tuned run diverge from the int32 default
        t = TuningTable()
        with pytest.raises(ValueError, match="unsafe"):
            t.record(1024, 3, 127, LCSTuning("int8"))
        t.record(1024, 3, 127, LCSTuning("int32"))  # int32 fine
        t.record(1024, 3, 126, LCSTuning("int8"))   # short L fine


class TestRoundTrip:
    def test_save_load(self, tmp_path):
        path = tmp_path / "TUNING.json"
        t = _table_with({
            (4096, 3, 32): LCSTuning("int8", pairs_per_sec=1e5),
            (1024, 3, 16): LCSTuning("int32"),
        })
        t.save(path)
        back = TuningTable.load(path)
        assert back.entries == t.entries
        assert back.lookup(4096, 3, 32) == LCSTuning("int8", 1e5)

    def test_env_path_override(self, tmp_path, monkeypatch):
        p = tmp_path / "elsewhere.json"
        monkeypatch.setenv("REPRO_TUNING_PATH", str(p))
        assert tuning_path() == p
        _table_with({(64, 3, 16): LCSTuning("int32")}).save()
        assert p.exists()
        assert TuningTable.load().lookup(64, 3, 16) is not None


class TestInvalidation:
    """Every mismatch degrades to the EMPTY table, never a partial one."""

    def _saved(self, tmp_path):
        path = tmp_path / "TUNING.json"
        _table_with({(4096, 3, 32): LCSTuning("int8")}).save(path)
        return path

    def test_missing_file(self, tmp_path):
        assert TuningTable.load(tmp_path / "nope.json").entries == {}

    def test_corrupt_json(self, tmp_path):
        path = tmp_path / "TUNING.json"
        path.write_text("{not json")
        assert TuningTable.load(path).entries == {}

    @pytest.mark.parametrize("field,value", [
        ("schema", "repro-tuning/v0"),
        ("jax_version", "0.0.1"),
        ("backend", "not-a-backend"),
    ])
    def test_header_mismatch(self, tmp_path, field, value):
        path = self._saved(tmp_path)
        raw = json.loads(path.read_text())
        assert raw["schema"] == SCHEMA
        raw[field] = value
        path.write_text(json.dumps(raw))
        assert TuningTable.load(path).entries == {}

    def test_corrupt_cell_discards_whole_table(self, tmp_path):
        path = self._saved(tmp_path)
        raw = json.loads(path.read_text())
        key = next(iter(raw["entries"]))
        raw["entries"]["P64-H3-L16-cpu"] = {"wavefront_dtype": "int16"}
        path.write_text(json.dumps(raw))
        t = TuningTable.load(path)
        assert t.entries == {}          # the GOOD cell is gone too
        assert key not in t.entries


class TestLookup:
    def test_exact_hit_is_p_quantized(self):
        t = _table_with({(4096, 3, 32): LCSTuning("int8")})
        # 3000 quantizes to the same P4096 buffer the planner would pad to
        assert t.lookup(3000, 3, 32) == LCSTuning("int8")

    def test_nearest_p_fallback(self):
        t = _table_with({
            (1024, 3, 32): LCSTuning("int8", pairs_per_sec=1.0),
            (65536, 3, 32): LCSTuning("int8", pairs_per_sec=2.0),
        })
        assert t.lookup(2048, 3, 32) == LCSTuning("int8", pairs_per_sec=1.0)
        assert t.lookup(32768, 3, 32) == LCSTuning("int8", pairs_per_sec=2.0)

    def test_miss_on_different_shape(self):
        t = _table_with({(4096, 3, 32): LCSTuning("int8")})
        assert t.lookup(4096, 5, 32) is None   # H differs
        assert t.lookup(4096, 3, 64) is None   # L differs


class TestDtypeResolution:
    def test_untuned_falls_back_to_env_default(self, monkeypatch):
        monkeypatch.delenv("REPRO_LCS_DTYPE", raising=False)
        from repro.core.similarity import wavefront_dtype_from_env

        assert resolve_wavefront_dtype(None) == wavefront_dtype_from_env()

    def test_tuned_dtype_wins_when_unpinned(self, monkeypatch):
        monkeypatch.delenv("REPRO_LCS_DTYPE", raising=False)
        assert resolve_wavefront_dtype(LCSTuning("int32")) == jnp.int32
        assert resolve_wavefront_dtype(LCSTuning("int8")) == jnp.int8

    def test_env_pin_outranks_tuned(self, monkeypatch):
        # the reproducibility knob beats the performance knob
        monkeypatch.setenv("REPRO_LCS_DTYPE", "int32")
        assert resolve_wavefront_dtype(LCSTuning("int8")) == jnp.int32
        monkeypatch.setenv("REPRO_LCS_DTYPE", "int8")
        assert resolve_wavefront_dtype(LCSTuning("int32")) == jnp.int8


class TestPlannerPlumbing:
    def test_autotune_off_returns_none(self, tmp_path, monkeypatch):
        from repro.api import CapacityPlanner

        # even with a live table on disk: plans must not probe it unasked
        monkeypatch.setenv("REPRO_TUNING_PATH", str(tmp_path / "T.json"))
        _table_with({(4096, 3, 32): LCSTuning("int8")}).save()
        assert CapacityPlanner().plan_tuning(4096, 3, 32) is None

    def test_autotune_on_reads_table(self, tmp_path, monkeypatch):
        from repro.api import CapacityPlanner

        monkeypatch.setenv("REPRO_TUNING_PATH", str(tmp_path / "T.json"))
        _table_with({(4096, 3, 32): LCSTuning("int8")}).save()
        planner = CapacityPlanner(autotune=True)
        assert planner.plan_tuning(4096, 3, 32) == LCSTuning("int8")
        assert planner.plan_tuning(4096, 9, 32) is None  # miss -> defaults

    def test_execution_plan_flags(self):
        from repro.api import ExecutionPlan

        assert ExecutionPlan().autotune is False
        assert ExecutionPlan().overlap_chunks == 1
        ExecutionPlan(overlap_chunks=4)     # pow2 accepted
        with pytest.raises(ValueError, match="power of two"):
            ExecutionPlan(overlap_chunks=3)
        with pytest.raises(ValueError, match="power of two"):
            ExecutionPlan(overlap_chunks=0)


class TestTunedDispatchParity:
    def test_tuned_lcs_bit_identical(self):
        """A tuned dtype through ops.lcs matches the default."""
        import numpy as np

        from repro.kernels.lcs import ops as lcs_ops

        rng = np.random.default_rng(0)
        B, L = 300, 12
        a = rng.integers(0, 6, size=(B, L)).astype(np.int32)
        b = rng.integers(0, 6, size=(B, L)).astype(np.int32)
        base = np.asarray(lcs_ops.lcs(jnp.asarray(a), jnp.asarray(b)))
        for t in (LCSTuning("int8"), LCSTuning("int32")):
            got = np.asarray(lcs_ops.lcs(
                jnp.asarray(a), jnp.asarray(b), mode="wavefront",
                wavefront_dtype=resolve_wavefront_dtype(t),
            ))
            np.testing.assert_array_equal(got, base)
