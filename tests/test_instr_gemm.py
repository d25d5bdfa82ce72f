"""GPETPU instruction set semantics + GEMM lowerings (paper §5, §7.1)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import gemm, instr as I
from repro.core import tensorizer as tz

RNG = np.random.default_rng(7)


class TestInstructions:
    def test_fp_semantics(self):
        a = RNG.normal(size=(16, 16)).astype(np.float32)
        b = RNG.normal(size=(16, 16)).astype(np.float32)
        np.testing.assert_allclose(I.invoke(I.Instr.ADD, a, b, quantized=False), a + b, rtol=1e-6)
        np.testing.assert_allclose(I.invoke(I.Instr.SUB, a, b, quantized=False), a - b, rtol=1e-6)
        np.testing.assert_allclose(I.invoke(I.Instr.MUL, a, b, quantized=False), a * b, rtol=1e-6)
        np.testing.assert_allclose(I.invoke(I.Instr.MEAN, a, quantized=False), a.mean(), rtol=1e-5)
        np.testing.assert_allclose(I.invoke(I.Instr.MAX, a, quantized=False), a.max(), rtol=1e-6)

    def test_quant_close_to_fp(self):
        a = RNG.uniform(0, 8, (32, 32)).astype(np.float32)
        b = RNG.uniform(0, 8, (32, 32)).astype(np.float32)
        for op, ref in [(I.Instr.ADD, a + b), (I.Instr.SUB, a - b), (I.Instr.MUL, a * b)]:
            out = np.asarray(I.invoke(op, a, b, quantized=True))
            scale = np.abs(ref).max() + 1e-9
            assert np.abs(out - ref).max() / scale < 0.03, op

    def test_matrixwise_quant(self):
        a = RNG.uniform(-2, 2, (100, 70)).astype(np.float32)
        assert abs(float(I.mean_quant(jnp.asarray(a))) - a.mean()) < 0.05
        assert abs(float(I.max_quant(jnp.asarray(a))) - a.max()) < 0.05

    def test_conv2d_quant(self):
        x = RNG.uniform(-2, 2, (64, 64)).astype(np.float32)
        k = RNG.normal(size=(3, 3)).astype(np.float32)
        out = np.asarray(I.conv2d_quant(jnp.asarray(x), jnp.asarray(k)))
        ref = np.asarray(I.conv2d_fp(jnp.asarray(x), jnp.asarray(k)))
        assert np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9) < 0.05

    def test_crop_ext(self):
        x = RNG.normal(size=(10, 13)).astype(np.float32)
        padded = I.invoke(I.Instr.EXT, x, quantized=False)
        assert padded.shape == (128, 128)
        back = I.invoke(I.Instr.CROP, padded, 10, 13, quantized=False)
        np.testing.assert_array_equal(np.asarray(back), x)


class TestGemmLowerings:
    @pytest.mark.parametrize("M,K,N", [(64, 64, 64), (100, 70, 90), (129, 257, 65)])
    def test_conv2d_lowering_fp_exact(self, M, K, N):
        """The conv2D-strided GEMM (paper §7.1.2) is EXACTLY GEMM in fp."""
        a = RNG.normal(size=(M, K)).astype(np.float32)
        b = RNG.normal(size=(K, N)).astype(np.float32)
        out = np.asarray(gemm.gemm_conv2d(jnp.asarray(a), jnp.asarray(b), quantized=False))
        np.testing.assert_allclose(out, a @ b, rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("M,K,N", [(64, 64, 64), (100, 70, 90)])
    def test_lowerings_agree(self, M, K, N):
        a = RNG.uniform(0, 4, (M, K)).astype(np.float32)
        b = RNG.uniform(0, 4, (K, N)).astype(np.float32)
        exact = a.astype(np.float64) @ b.astype(np.float64)
        rel = lambda o: np.abs(o - exact).max() / np.abs(exact).max()
        fc = np.asarray(gemm.gemm_fully_connected(jnp.asarray(a), jnp.asarray(b)))
        cv = np.asarray(gemm.gemm_conv2d(jnp.asarray(a), jnp.asarray(b)))
        assert rel(fc) < 0.02 and rel(cv) < 0.02

    def test_kernel_path_matches_einsum_path(self):
        a = RNG.uniform(-2, 2, (100, 70)).astype(np.float32)
        b = RNG.uniform(-2, 2, (70, 90)).astype(np.float32)
        k = np.asarray(gemm.gemm_fully_connected(jnp.asarray(a), jnp.asarray(b), use_kernel=True))
        e = np.asarray(gemm.gemm_fully_connected(jnp.asarray(a), jnp.asarray(b), use_kernel=False))
        np.testing.assert_allclose(k, e, rtol=2e-3, atol=2e-3)

    def test_tpu_gemm_auto_lowering(self):
        a = RNG.uniform(0, 4, (64, 64)).astype(np.float32)
        b = RNG.uniform(0, 4, (64, 64)).astype(np.float32)
        out = np.asarray(gemm.tpu_gemm(jnp.asarray(a), jnp.asarray(b)))
        exact = a @ b
        assert np.abs(out - exact).max() / np.abs(exact).max() < 0.02


def test_instr_table_is_keyed_by_device_kind(tmp_path, monkeypatch):
    """A cost table measured on one device kind is never read on another:
    the file keeps one table per kind, and a missing kind is measured."""
    import json

    import jax

    from repro.core import instr_select as IS

    path = tmp_path / "instr_table.json"
    other = {"gemm_conv2d": {"results_per_s": 1e12}}
    path.write_text(json.dumps({"some other kind": other}))
    here = {"gemm_fully_connected": {"results_per_s": 1.0}}
    monkeypatch.setenv(IS._CACHE_ENV, str(path))
    monkeypatch.setattr(IS, "_tables", {})
    monkeypatch.setattr(IS, "build_table", lambda: here)
    assert IS.get_table() == here
    assert IS.best_gemm_lowering() == "fully_connected"
    stored = json.loads(path.read_text())
    assert stored == {"some other kind": other,
                      jax.devices()[0].device_kind: here}
