"""Property-based differential tests of the CSR indirection-stream kernels.

Random CSR patterns — empty rows, single-element rows, all-zero matrices,
densities across {0.01, 0.1, 0.5}, ragged row populations, non-square
shapes — drive SpMV/SpMM through the compiled gather path and compare
against the densified ``jnp.dot`` oracle to ≤ 1e-5.  Malformed CSR must
fail loudly with the pinned ``ValueError`` messages (they are API surface).
The dispatch tests pin the zero-overhead contract (a repeated call moves
no build/trace counters) and the schedule-cache transparency contract (a
tuned schedule committed under the kernel's own lookup key is picked up
with no call-site changes, and never changes the numbers).
"""

import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from hypothesis import given, settings
import hypothesis.strategies as st

from repro.core import DEFAULT_SCHEDULE, autotune, compiler
from repro.core.lowering import plan_stats
from repro.core.nest_analysis import auto_lanes
from repro.kernels import frontend, ops
from repro.kernels import sparse as sp

#: The differential-agreement bound of the whole suite (ISSUE acceptance):
#: streamed gather vs densified ``jnp.dot``, both in f32.
TOL = 1e-5

#: The densities the strategies sweep — sparse enough for empty rows to be
#: common, dense enough to exercise multi-element rows.
DENSITIES = (0.01, 0.1, 0.5)


def _assert_close(got, want, tol=TOL):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, (got.shape, want.shape)
    if got.size:
        assert float(np.max(np.abs(got - want))) <= tol


# --------------------------------------------------------------------------
# CSR strategies
# --------------------------------------------------------------------------


@st.composite
def csr_patterns(draw, max_m=9, max_n=12):
    """A random valid CSR triple + its column count.

    Row populations are drawn independently per row (ragged by
    construction), biased by a density drawn from :data:`DENSITIES`; a row
    budget of zero yields empty rows, and density 0.01 on these small
    shapes yields entirely zero matrices — the edge cases ride in the
    distribution instead of being bolted on.
    """
    m = draw(st.integers(min_value=1, max_value=max_m))
    n = draw(st.integers(min_value=1, max_value=max_n))
    density = draw(st.sampled_from(list(DENSITIES)))
    data, indices, indptr = [], [], [0]
    for _ in range(m):
        cap = max(0, round(n * density))
        if cap and draw(st.booleans()):
            cap = max(1, cap - 1)  # jitter the row budget: ragged rows
        cols = sorted(draw(st.lists(
            st.integers(min_value=0, max_value=n - 1),
            min_size=0, max_size=cap, unique=True)))
        for c in cols:
            indices.append(c)
            data.append(draw(st.floats(min_value=-2.0, max_value=2.0)))
        indptr.append(len(indices))
    return (np.asarray(data, np.float32), np.asarray(indices, np.int64),
            np.asarray(indptr, np.int64), n)


def _dense_ref_spmv(data, indices, indptr, x):
    dense = sp.csr_to_dense(data, indices, indptr, x.shape[0])
    return jnp.dot(jnp.asarray(dense), jnp.asarray(x, jnp.float32))


def _dense_ref_spmm(data, indices, indptr, x):
    dense = sp.csr_to_dense(data, indices, indptr, x.shape[0])
    return jnp.dot(jnp.asarray(dense), jnp.asarray(x, jnp.float32))


# --------------------------------------------------------------------------
# Differential properties: streamed gather vs densified oracle
# --------------------------------------------------------------------------


class TestSpmvDifferential:
    @settings(max_examples=30, deadline=None)
    @given(csr=csr_patterns())
    def test_matches_densified_dot(self, csr):
        data, indices, indptr, n = csr
        rng = np.random.default_rng(n * 1000 + data.size)
        x = rng.standard_normal(n).astype(np.float32)
        _assert_close(sp.ssr_spmv(data, indices, indptr, x),
                      _dense_ref_spmv(data, indices, indptr, x))

    @settings(max_examples=15, deadline=None)
    @given(csr=csr_patterns())
    def test_baseline_matches_densified_dot(self, csr):
        data, indices, indptr, n = csr
        rng = np.random.default_rng(n * 77 + data.size)
        x = rng.standard_normal(n).astype(np.float32)
        _assert_close(sp.baseline_spmv(data, indices, indptr, x),
                      _dense_ref_spmv(data, indices, indptr, x))


class TestSpmmDifferential:
    @settings(max_examples=30, deadline=None)
    @given(csr=csr_patterns(), c=st.integers(min_value=1, max_value=5))
    def test_matches_densified_dot(self, csr, c):
        data, indices, indptr, n = csr
        rng = np.random.default_rng(n * 1000 + c)
        x = rng.standard_normal((n, c)).astype(np.float32)
        _assert_close(sp.ssr_spmm(data, indices, indptr, x),
                      _dense_ref_spmm(data, indices, indptr, x))

    @settings(max_examples=15, deadline=None)
    @given(csr=csr_patterns(), c=st.integers(min_value=1, max_value=5))
    def test_baseline_matches_densified_dot(self, csr, c):
        data, indices, indptr, n = csr
        rng = np.random.default_rng(n * 77 + c)
        x = rng.standard_normal((n, c)).astype(np.float32)
        _assert_close(sp.baseline_spmm(data, indices, indptr, x),
                      _dense_ref_spmm(data, indices, indptr, x))


class TestDeterministicEdgeCases:
    """The named edge shapes, pinned so a strategy change can't lose them."""

    def _roundtrip(self, data, indices, indptr, n):
        x = np.linspace(-1, 1, n, dtype=np.float32)
        X = np.linspace(-1, 1, 3 * n, dtype=np.float32).reshape(n, 3)
        _assert_close(sp.ssr_spmv(data, indices, indptr, x),
                      _dense_ref_spmv(data, indices, indptr, x))
        _assert_close(sp.ssr_spmm(data, indices, indptr, X),
                      _dense_ref_spmm(data, indices, indptr, X))

    def test_all_zero_matrix(self):
        self._roundtrip(np.zeros(0, np.float32), np.zeros(0, np.int64),
                        np.zeros(5, np.int64), 7)

    def test_every_row_single_element(self):
        self._roundtrip(np.asarray([1.5, -2.0, 0.25], np.float32),
                        np.asarray([4, 0, 2], np.int64),
                        np.asarray([0, 1, 2, 3], np.int64), 6)

    def test_mixed_empty_and_full_rows(self):
        self._roundtrip(np.asarray([1.0, 2.0, 3.0, 4.0], np.float32),
                        np.asarray([0, 1, 2, 3], np.int64),
                        np.asarray([0, 0, 4, 4], np.int64), 4)

    def test_one_by_one(self):
        self._roundtrip(np.asarray([3.0], np.float32),
                        np.asarray([0], np.int64),
                        np.asarray([0, 1], np.int64), 1)

    def test_tall_and_wide(self):
        rng = np.random.default_rng(11)
        for m, n in ((17, 3), (3, 17)):
            data, indices, indptr = sp.random_csr(rng, m, n, 0.3)
            self._roundtrip(data, indices, indptr, n)

    def test_sparse_gemv_alias(self):
        rng = np.random.default_rng(5)
        data, indices, indptr = sp.random_csr(rng, 8, 10, 0.2)
        x = rng.standard_normal(10).astype(np.float32)
        _assert_close(sp.ssr_sparse_gemv(data, indices, indptr, x),
                      sp.ssr_spmv(data, indices, indptr, x), tol=0.0)

    def test_ops_facades_agree_across_ssrcfg(self):
        rng = np.random.default_rng(6)
        data, indices, indptr = sp.random_csr(rng, 9, 11, 0.2)
        x = rng.standard_normal(11).astype(np.float32)
        X = rng.standard_normal((11, 4)).astype(np.float32)
        _assert_close(ops.spmv(data, indices, indptr, x, ssr=True),
                      ops.spmv(data, indices, indptr, x, ssr=False))
        _assert_close(ops.spmm(data, indices, indptr, X, ssr=True),
                      ops.spmm(data, indices, indptr, X, ssr=False))
        _assert_close(ops.sparse_gemv(data, indices, indptr, x, ssr=True),
                      ops.spmv(data, indices, indptr, x, ssr=True), tol=0.0)


# --------------------------------------------------------------------------
# Malformed CSR: loud, pinned failures
# --------------------------------------------------------------------------

_GOOD = (np.asarray([1.0, 2.0, 3.0], np.float32),
         np.asarray([0, 2, 1], np.int64),
         np.asarray([0, 2, 3], np.int64), 4)


class TestInvalidCsr:
    def _x(self):
        return np.ones(_GOOD[3], np.float32)

    def test_good_baseline_is_valid(self):
        sp.validate_csr(*_GOOD)

    def test_short_indptr(self):
        with pytest.raises(ValueError,
                           match="indptr must be 1-D with at least two"):
            sp.validate_csr(_GOOD[0], _GOOD[1], np.asarray([0]), 4)

    def test_two_dimensional_indptr(self):
        with pytest.raises(ValueError,
                           match="indptr must be 1-D with at least two"):
            sp.validate_csr(_GOOD[0], _GOOD[1], np.zeros((2, 2), np.int64), 4)

    def test_data_indices_length_mismatch(self):
        with pytest.raises(ValueError,
                           match="data and indices must be 1-D of equal"):
            sp.validate_csr(_GOOD[0][:2], _GOOD[1], _GOOD[2], 4)

    def test_non_monotone_indptr(self):
        with pytest.raises(ValueError, match="indptr must be non-decreasing"):
            sp.validate_csr(_GOOD[0], _GOOD[1],
                            np.asarray([0, 3, 2]), 4)
        # non-monotone must win over the endpoint check even when the
        # endpoints happen to be right
        with pytest.raises(ValueError, match="indptr must be non-decreasing"):
            sp.ssr_spmv(_GOOD[0], _GOOD[1], np.asarray([0, 3, 1, 3]),
                        self._x())

    def test_bad_indptr_endpoints(self):
        with pytest.raises(ValueError,
                           match="indptr must start at 0 and end at nnz"):
            sp.validate_csr(_GOOD[0], _GOOD[1], np.asarray([1, 2, 3]), 4)
        with pytest.raises(ValueError,
                           match="indptr must start at 0 and end at nnz"):
            sp.validate_csr(_GOOD[0], _GOOD[1], np.asarray([0, 2, 5]), 4)

    def test_column_index_out_of_range(self):
        with pytest.raises(ValueError, match="column index out of range"):
            sp.validate_csr(_GOOD[0], np.asarray([0, 9, 1]), _GOOD[2], 4)
        with pytest.raises(ValueError, match="column index out of range"):
            sp.validate_csr(_GOOD[0], np.asarray([0, -1, 1]), _GOOD[2], 4)

    def test_unsorted_within_row(self):
        with pytest.raises(
                ValueError,
                match="column indices must be strictly increasing within"):
            sp.validate_csr(np.asarray([1.0, 2.0, 3.0]),
                            np.asarray([2, 0, 1]),
                            np.asarray([0, 3, 3]), 4)

    def test_duplicate_within_row(self):
        with pytest.raises(
                ValueError,
                match="column indices must be strictly increasing within"):
            sp.validate_csr(np.asarray([1.0, 2.0]),
                            np.asarray([1, 1]),
                            np.asarray([0, 2]), 4)

    def test_descending_across_row_boundary_is_fine(self):
        # row 0 ends at col 3, row 1 starts at col 0: legal CSR
        sp.validate_csr(np.asarray([1.0, 2.0]),
                        np.asarray([3, 0]),
                        np.asarray([0, 1, 2]), 4)

    def test_all_entry_points_validate(self):
        bad_indptr = np.asarray([0, 3, 2])
        for fn in (sp.ssr_spmv, sp.baseline_spmv, sp.ref_spmv):
            with pytest.raises(ValueError,
                               match="indptr must be non-decreasing"):
                fn(_GOOD[0], _GOOD[1], bad_indptr, self._x())
        X = np.ones((_GOOD[3], 2), np.float32)
        for fn in (sp.ssr_spmm, sp.baseline_spmm, sp.ref_spmm):
            with pytest.raises(ValueError,
                               match="indptr must be non-decreasing"):
                fn(_GOOD[0], _GOOD[1], bad_indptr, X)

    def test_spmm_rejects_vector_operand(self):
        with pytest.raises(ValueError, match="dense \\(n, c\\) operand"):
            sp.ssr_spmm(_GOOD[0], _GOOD[1], _GOOD[2], self._x())

    @settings(max_examples=10, deadline=None)
    @given(csr=csr_patterns())
    def test_generated_patterns_are_valid(self, csr):
        data, indices, indptr, n = csr
        _, _, _, m = sp.validate_csr(data, indices, indptr, n)
        assert m == indptr.size - 1


# --------------------------------------------------------------------------
# Cost model: the eliminated index-handling instructions (Eq. (1)–(3) ext.)
# --------------------------------------------------------------------------


class TestIndirectionCostModel:
    def test_spmv_eliminates_two_instrs_per_nnz_slot(self):
        m, k = 16, 6
        nest = compiler.spmv_nest(m, k)
        stats = plan_stats(nest, num_lanes=auto_lanes(nest))
        assert stats.ssrified
        # one index load + one pointer-arith op per (row, slot) visit
        assert stats.eliminated_idx_instrs == 2 * m * k
        assert stats.n_base > stats.n_ssr

    def test_spmm_eliminates_per_column_revisit(self):
        m, c, k = 8, 4, 5
        nest = compiler.spmm_nest(m, c, k, 128)
        stats = plan_stats(nest, num_lanes=auto_lanes(nest))
        assert stats.ssrified
        # the gather's depth tracks its index stream (innermost), so the
        # per-nnz index handling is re-paid for every dense column c
        assert stats.eliminated_idx_instrs == 2 * m * c * k
        assert stats.n_base > stats.n_ssr

    def test_dense_nests_eliminate_nothing(self):
        nest = compiler.gemm_nest(8, 8, 8)
        stats = plan_stats(nest, num_lanes=auto_lanes(nest))
        assert stats.ssrified
        assert stats.eliminated_idx_instrs == 0


# --------------------------------------------------------------------------
# Dispatch contracts: zero overhead + transparent schedule-cache pickup
# --------------------------------------------------------------------------


class TestDispatch:
    def test_repeated_call_is_pure_cache_hit(self):
        rng = np.random.default_rng(3)
        data, indices, indptr = sp.random_csr(rng, 10, 12, 0.2)
        x = rng.standard_normal(12).astype(np.float32)
        first = sp.ssr_spmv(data, indices, indptr, x)
        snap = dict(frontend.DISPATCH_STATS)
        again = sp.ssr_spmv(data, indices, indptr, x)
        assert frontend.DISPATCH_STATS["builds"] == snap["builds"]
        assert frontend.DISPATCH_STATS["traces"] == snap["traces"]
        assert frontend.DISPATCH_STATS["calls"] == snap["calls"] + 1
        _assert_close(again, first, tol=0.0)

    def test_spmm_repeated_call_is_pure_cache_hit(self):
        rng = np.random.default_rng(4)
        data, indices, indptr = sp.random_csr(rng, 8, 9, 0.25)
        X = rng.standard_normal((9, 3)).astype(np.float32)
        first = sp.ssr_spmm(data, indices, indptr, X)
        snap = dict(frontend.DISPATCH_STATS)
        again = sp.ssr_spmm(data, indices, indptr, X)
        assert frontend.DISPATCH_STATS["builds"] == snap["builds"]
        assert frontend.DISPATCH_STATS["traces"] == snap["traces"]
        _assert_close(again, first, tol=0.0)

    def test_tuned_schedule_resolved_transparently(self, tmp_path,
                                                   monkeypatch):
        """A winner committed under the kernel's own lookup key is what the
        next call runs — no call-site changes — and the numbers match."""
        monkeypatch.setenv("REPRO_SCHEDULE_CACHE", str(tmp_path))
        rng = np.random.default_rng(9)
        data, indices, indptr = sp.random_csr(rng, 12, 14, 0.3)
        x = rng.standard_normal(14).astype(np.float32)
        want = _dense_ref_spmv(data, indices, indptr, x)

        vals, cidx, m, k = sp.csr_to_ell(data, indices, indptr, 14)
        args = (jnp.asarray(vals), jnp.asarray(cidx),
                jnp.asarray(x, jnp.float32))
        params = {"m": m, "k": k}
        assert sp._ssr_spmv.schedule_for(*args, **params) == DEFAULT_SCHEDULE

        operands, static, _final = sp._ssr_spmv._prepare(*args, **params)
        nest = sp._ssr_spmv._nest(static)
        variant = dataclasses.replace(DEFAULT_SCHEDULE, rows=4)
        ok, why = autotune.schedule_is_legal(nest, variant,
                                             operands=dict(operands))
        assert ok, why
        key = autotune.cache_key(nest, dict(operands),
                                 mode=sp._ssr_spmv._mode,
                                 out_dtype="float32")
        autotune.global_cache().put(key, variant, meta={"test": True})

        assert sp._ssr_spmv.schedule_for(*args, **params) == variant
        _assert_close(sp.ssr_spmv(data, indices, indptr, x), want)

    def test_gather_tables_charge_the_vmem_budget(self):
        """Autotune legality: a huge gather table makes schedules illegal."""
        nest = compiler.spmv_nest(8, 4)
        ok, _ = autotune.schedule_is_legal(nest, DEFAULT_SCHEDULE)
        assert ok
        ok, why = autotune.schedule_is_legal(
            nest, DEFAULT_SCHEDULE,
            operands={"x": ((1 << 26,), "float32")})
        assert not ok and "VMEM" in why

    def test_ell_width_is_a_cache_key_fact(self):
        """Two same-shape CSRs with different max row population must not
        share a pipeline: k is a static param, so the call keys differ."""
        x = np.ones(6, np.float32)
        a = (np.asarray([1.0, 2.0], np.float32), np.asarray([0, 1]),
             np.asarray([0, 2, 2]))     # k = 2
        b = (np.asarray([1.0, 2.0], np.float32), np.asarray([0, 0]),
             np.asarray([0, 1, 2]))     # k = 1
        _assert_close(sp.ssr_spmv(*a, x), _dense_ref_spmv(*a, x))
        _assert_close(sp.ssr_spmv(*b, x), _dense_ref_spmv(*b, x))


# --------------------------------------------------------------------------
# Bench artifacts: schema-v5 sparse rows + the run-history sparse summary
# --------------------------------------------------------------------------


def _sparse_pair(kern, agree, speedup, nnz=100, density=0.1, idx=200):
    from benchmarks.kernel_bench import _row
    return [_row(f"sparse/{kern}", "sparse", "agreement", agree,
                 "max_abs_diff", nnz=nnz, density=density),
            _row(f"sparse/{kern}", "sparse", "model", speedup,
                 "model_speedup", nnz=nnz, density=density,
                 n_base=100, n_ssr=50, eliminated_idx_instrs=idx)]


class TestSparseBenchValidators:
    def test_accepts_good_pairs(self):
        from benchmarks import kernel_bench as kb
        rows = sum((_sparse_pair(k, 1e-7, 3.0) for k in kb.SPARSE_GATED), [])
        kb.validate_sparse_rows(rows)

    def test_rejects_disagreement(self):
        from benchmarks import kernel_bench as kb
        rows = sum((_sparse_pair(k, 1e-7, 3.0)
                    for k in kb.SPARSE_GATED[1:]), [])
        rows += _sparse_pair(kb.SPARSE_GATED[0], 1e-3, 3.0)
        with pytest.raises(ValueError, match="disagreement"):
            kb.validate_sparse_rows(rows)

    def test_rejects_unprofitable_model(self):
        from benchmarks import kernel_bench as kb
        rows = sum((_sparse_pair(k, 1e-7, 3.0)
                    for k in kb.SPARSE_GATED[1:]), [])
        rows += _sparse_pair(kb.SPARSE_GATED[0], 1e-7, 0.9)
        with pytest.raises(ValueError, match="model speedup"):
            kb.validate_sparse_rows(rows)

    def test_rejects_zero_eliminated_instrs(self):
        from benchmarks import kernel_bench as kb
        rows = sum((_sparse_pair(k, 1e-7, 3.0)
                    for k in kb.SPARSE_GATED[1:]), [])
        rows += _sparse_pair(kb.SPARSE_GATED[0], 1e-7, 3.0, idx=0)
        with pytest.raises(ValueError, match="eliminated_idx_instrs"):
            kb.validate_sparse_rows(rows)

    def test_requires_nnz_density_provenance(self):
        from benchmarks import kernel_bench as kb
        rows = sum((_sparse_pair(k, 1e-7, 3.0) for k in kb.SPARSE_GATED), [])
        del rows[0]["nnz"]
        with pytest.raises(ValueError, match="integer nnz"):
            kb.validate_sparse_rows(rows)
        rows = sum((_sparse_pair(k, 1e-7, 3.0, density=1.5)
                    for k in kb.SPARSE_GATED), [])
        with pytest.raises(ValueError, match="density outside"):
            kb.validate_sparse_rows(rows)

    def test_requires_all_gated_kernels(self):
        from benchmarks import kernel_bench as kb
        rows = _sparse_pair(kb.SPARSE_GATED[0], 1e-7, 3.0)
        with pytest.raises(ValueError, match="no sparse gate rows"):
            kb.validate_sparse_rows(rows)

    def test_history_line_carries_sparse_summary(self, tmp_path):
        from benchmarks import kernel_bench as kb
        rows = sum((_sparse_pair(k, 1e-7, 3.0, nnz=42, density=0.25,
                                 idx=84) for k in kb.SPARSE_GATED), [])
        path = str(tmp_path / "hist.jsonl")
        entry = kb.append_bench_history(rows, path, quick=True)
        assert entry["schema"] == kb.BENCH_SCHEMA == 6
        assert set(entry["sparse"]) == set(kb.SPARSE_GATED)
        for info in entry["sparse"].values():
            assert info == {"nnz": 42, "density": 0.25,
                            "eliminated_idx_instrs": 84}
        assert kb.validate_bench_history(path) == 1

    def test_history_rejects_mistyped_sparse_summary(self, tmp_path):
        import json

        from benchmarks import kernel_bench as kb
        rows = sum((_sparse_pair(k, 1e-7, 3.0) for k in kb.SPARSE_GATED), [])
        path = str(tmp_path / "hist.jsonl")
        kb.append_bench_history(rows, path, quick=True)
        with open(path) as f:
            entry = json.loads(f.readline())
        entry["sparse"]["spmv"] = {"density": 0.1}   # nnz missing
        with open(path, "w") as f:
            f.write(json.dumps(entry) + "\n")
        with pytest.raises(ValueError, match="missing integer nnz"):
            kb.validate_bench_history(path)

    def test_history_without_sparse_field_stays_valid(self, tmp_path):
        """Pre-v5 lines legitimately lack the sparse summary."""
        import json

        from benchmarks import kernel_bench as kb
        path = str(tmp_path / "hist.jsonl")
        old = {"schema": 4, "date": "2026-01-01T00:00:00Z",
               "git_sha": "abc1234", "quick": False, "rows": 3,
               "groups": ["dag"], "speedups": {}, "dag_cuts": {}}
        with open(path, "w") as f:
            f.write(json.dumps(old) + "\n")
        assert kb.validate_bench_history(path) == 1


class TestDeclaredOffChip:
    """Mosaic refuses the flattened-table gather: both kernels are declared.

    Off the interpreter the streamed variant raises the named error before
    lowering — never a quiet interpret run or a hand-off to ``ref``.
    """

    @pytest.mark.parametrize("name", ["spmv", "spmm"])
    def test_mosaic_refusal_is_named(self, name):
        from repro.kernels import registry

        entry = registry.get(name)
        assert entry.mosaic_refusal == sp.MOSAIC_REFUSAL
        args, kw = entry.example(np.random.default_rng(0))
        snap = dict(frontend.DISPATCH_STATS)
        with pytest.raises(frontend.MosaicUnsupported,
                           match="Only 2D gather is supported"):
            entry.ssr(*args, interpret=False, **kw)
        assert frontend.DISPATCH_STATS["builds"] == snap["builds"]
