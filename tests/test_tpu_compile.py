"""Ahead-of-time compiles for a described TPU v5e 2x2 (no chip attached).

The TPU compiler refuses what interpret mode lets through: a ref kept in the
ANY memory space and read as a scalar, a lane block that is not a multiple
of 128, more scoped VMEM than the kernel asked for.  These tests lower and
compile the fused AG+GEMM and GEMM+RS kernels for the chip at smollm-360m's
TP=4 MLP widths and check that Mosaic emitted the kernel.

The topology is described only inside the module fixture (never at import):
one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import backend
from repro.core.channels import BlockChannel
from repro.core.compiler import compile_overlap

WORLD = 4
TOKENS, D, F = 2048, 960, 2560  # 2048 tokens through smollm-360m's MLP


@pytest.fixture(scope="module")
def mesh(tmp_path_factory):
    from jax.experimental import topologies

    # the TPU library otherwise writes its logs to /tmp/tpu_logs
    os.environ.setdefault("TPU_LOG_DIR", str(tmp_path_factory.mktemp("tpu_logs")))
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compile cannot be read back from the persistent cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield Mesh(np.asarray(topo.devices), ("model",))
    jax.config.update("jax_enable_compilation_cache", prev)


CASES = {
    # gate/up projection: x [T, d] row-sharded, w [d, 2f] column-sharded
    "ag_matmul": (((TOKENS, D), (D, 2 * F)), (P("model", None), P(None, "model")),
                  P(None, "model")),
    # down projection: x [T, f] column-sharded, w [f, d] row-sharded
    "matmul_rs": (((TOKENS, F), (F, D)), (P(None, "model"), P("model", None)),
                  P("model", None)),
}


@pytest.mark.parametrize("kind,channels", [("ag_matmul", 1), ("matmul_rs", 1),
                                           ("ag_matmul", 2), ("matmul_rs", 2)])
def test_fused_kernel_compiles_for_v5e(mesh, monkeypatch, kind, channels):
    monkeypatch.setenv("REPRO_BACKEND", "tpu")
    shapes, in_specs, out_spec = CASES[kind]
    fn = compile_overlap(kind, BlockChannel(axis="model", num_channels=channels),
                         backend="pallas", world_size=WORLD)
    sm = backend.shard_map(fn, mesh, in_specs=in_specs, out_specs=out_spec)
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=NamedSharding(mesh, sp))
            for s, sp in zip(shapes, in_specs)]
    compiled = jax.jit(sm).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
