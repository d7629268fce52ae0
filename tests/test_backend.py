"""Backend layer tests: emulated-target kernel/oracle parity + import hygiene.

Two jobs:

  1. every public kernel builds and matches its ref.py oracle with the
     backend forced to the ``emulated`` target (interpret on CPU) — the
     configuration CI runs on any JAX without a TPU;
  2. a guard that greps ``src/repro`` for direct
     ``jax.experimental.pallas.tpu`` imports outside ``repro/backend/`` —
     the backend package is the single point of version adaptation, and
     drift regressions start with someone re-importing pltpu in a kernel.
"""
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import backend, kernels
from repro.kernels import ref
from utils import allclose

KEY = jax.random.PRNGKey(0)
SRC_ROOT = pathlib.Path(__file__).resolve().parent.parent / "src" / "repro"


@pytest.fixture()
def emulated_target(monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "emulated")
    assert backend.target() == "emulated"
    yield


# ---- surface sanity ----------------------------------------------------------

def test_describe_reports_probes():
    info = backend.describe()
    assert info["jax"] == jax.__version__
    assert info["platform"] == jax.devices()[0].platform
    assert info["device_count"] == jax.device_count()
    assert info["target"] == backend.target()


def test_compiler_params_drops_unknown_fields():
    # a misspelled field raises instead of being dropped in silence
    params = backend.compiler_params(
        dimension_semantics=("parallel",), vmem_limit_bytes=32 * 2**20
    )
    assert params.dimension_semantics == ("parallel",)
    assert params.vmem_limit_bytes == 32 * 2**20
    with pytest.raises(TypeError):
        backend.compiler_params(not_a_real_field_ever=1)


def test_target_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "tpu")
    assert backend.target() == "tpu"
    monkeypatch.setenv("REPRO_BACKEND", "emulated")
    assert backend.is_emulated()
    monkeypatch.setenv("REPRO_BACKEND", "bogus")
    with pytest.raises(ValueError):
        backend.target()


def test_resolve_interpret_emulated_forces_interpret(monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "emulated")
    assert backend.resolve_interpret(None) is not False
    # even an explicit compile request cannot compile without a TPU toolchain
    assert backend.resolve_interpret(False) is not False
    assert backend.default_interpret() is True


def test_chip_table_refuses_unknown_kind(monkeypatch):
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        backend.chip("TPU v99")
    # the tpu target reads the live device's kind: a CPU is not in the table
    monkeypatch.setenv("REPRO_BACKEND", "tpu")
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        backend.vmem_budget_bytes()
    # the emulated target models one named chip
    monkeypatch.setenv("REPRO_BACKEND", "emulated")
    assert backend.device_kind() == "TPU v5 lite"
    assert backend.vmem_budget_bytes() == 16 * 2**20
    assert backend.chip().vmem_bytes == 128 * 2**20


def test_roofline_peaks_keyed_by_device_kind():
    from repro.launch import roofline

    assert roofline.HW["TPU v5 lite"].peak_flops == 197e12
    terms = roofline.roofline_terms({"flops": 197e12, "bytes accessed": 819e9}, 50e9,
                                    kind="TPU v5 lite")
    assert terms["compute_s"] == terms["memory_s"] == terms["collective_s"] == 1.0
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        roofline.roofline_terms({}, 0.0, kind="TPU v99")


def test_vmem_limit_only_above_the_default_scope():
    assert backend.vmem_array_bytes((512, 960), jnp.float32) == 512 * 1024 * 4
    assert backend.vmem_array_bytes((4, 9, 130), jnp.bfloat16) == 4 * 16 * 256 * 2
    assert backend.vmem_limit_bytes(8 * 2**20) is None
    assert backend.vmem_limit_bytes(20 * 2**20) == 25 * 2**20


def test_compile_cache_dir(monkeypatch, tmp_path):
    from repro.backend import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert backend.enable_compile_cache() == str(tmp_path)
    assert calls == []  # JAX reads the variable itself; no other directory
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = backend.enable_compile_cache()
    assert calls == [("jax_compilation_cache_dir", path)]
    assert path == str(compile_cache.CACHE_DIR) and path.endswith(".jax_cache")


# ---- every public kernel vs. its oracle under the emulated target ------------

def test_matmul_oracle_emulated(emulated_target):
    x = jax.random.normal(KEY, (256, 128), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 256), jnp.float32)
    allclose(kernels.matmul(x, w), ref.matmul_ref(x, w), atol=2e-4, rtol=2e-4)


def test_flash_attention_oracle_emulated(emulated_target):
    q = jax.random.normal(KEY, (2, 128, 64), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 64), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(3), (2, 128, 64), jnp.float32)
    y = kernels.flash_attention(q, k, v, causal=True)
    allclose(y, ref.flash_attention_ref(q, k, v, causal=True),
             atol=2e-4, rtol=2e-3)


def test_grouped_matmul_oracle_emulated(emulated_target):
    e, m, k, n, bm = 4, 256, 128, 128, 128
    tile_expert = jnp.array([1, 3], jnp.int32)
    x = jax.random.normal(KEY, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(4), (e, k, n), jnp.float32)
    y = kernels.grouped_matmul(x, w, tile_expert, tile=(bm, 128, 128))
    allclose(y, ref.grouped_matmul_ref(x, w, tile_expert, bm),
             atol=1e-4, rtol=1e-4)


def test_ssd_intra_chunk_oracle_emulated(emulated_target):
    t, q, p = 2, 16, 8
    cum = -jnp.abs(jax.random.normal(KEY, (t, q))).cumsum(axis=1)
    cb = jax.random.normal(jax.random.PRNGKey(9), (t, q, q)) * 0.3
    xdt = jax.random.normal(jax.random.PRNGKey(10), (t, q, p)) * 0.5
    y = kernels.ssd_intra_chunk(cum, cb, xdt)
    diff = cum[:, :, None] - cum[:, None, :]
    mask = np.tril(np.ones((q, q), bool))
    g = np.asarray(cb) * np.where(mask, np.exp(np.asarray(diff)), 0.0)
    allclose(y, np.einsum("tqk,tkp->tqp", g, np.asarray(xdt)),
             atol=1e-4, rtol=1e-3)


def test_ag_gemm_fused_oracle_emulated(emulated_target):
    r, m_loc, k, n_loc = 4, 16, 32, 128
    mesh = backend.make_mesh((r,), ("model",))
    x = jax.random.normal(KEY, (r * m_loc, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(11), (k, r * n_loc), jnp.float32)
    fn = backend.shard_map(
        lambda a, b: kernels.ag_gemm_shard(a, b, world_size=r, bn=128),
        mesh, in_specs=(P("model", None), P(None, "model")),
        out_specs=P(None, "model"))
    # global-product oracle (ref.ag_gemm_ref states the same spec shard-wise)
    allclose(jax.jit(fn)(x, w), x @ w, atol=1e-3, rtol=1e-3)


def test_gemm_rs_fused_oracle_emulated(emulated_target):
    r, m, k_loc, n = 4, 64, 32, 128
    mesh = backend.make_mesh((r,), ("model",))
    x = jax.random.normal(KEY, (m, r * k_loc), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(12), (r * k_loc, n), jnp.float32)
    fn = backend.shard_map(
        lambda a, b: kernels.gemm_rs_shard(a, b, world_size=r, bn=128),
        mesh, in_specs=(P(None, "model"), P("model", None)),
        out_specs=P("model", None))
    # global-product oracle (ref.gemm_rs_ref states the same spec shard-wise)
    allclose(jax.jit(fn)(x, w), x @ w, atol=1e-3, rtol=1e-3)


# ---- import hygiene guard ----------------------------------------------------

_FORBIDDEN = re.compile(
    r"(from\s+jax\.experimental\.pallas\s+import\s+[^\n]*\btpu\b"
    r"|jax\.experimental\.pallas\.tpu"
    r"|from\s+jax\.experimental\.pallas\.tpu\s+import)"
)


def test_no_pltpu_imports_outside_backend():
    """repro.backend is the only module allowed to touch pallas TPU API."""
    offenders = []
    for path in sorted(SRC_ROOT.rglob("*.py")):
        rel = path.relative_to(SRC_ROOT)
        if rel.parts[0] == "backend":
            continue
        for lineno, line in enumerate(
                path.read_text(encoding="utf-8").splitlines(), 1):
            code = line.split("#", 1)[0]
            if _FORBIDDEN.search(code):
                offenders.append(f"{rel}:{lineno}: {line.strip()}")
    assert not offenders, (
        "direct jax.experimental.pallas.tpu usage outside repro/backend/ "
        "(route through repro.backend instead):\n" + "\n".join(offenders)
    )
