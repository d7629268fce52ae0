"""Ahead-of-time compiles for a described TPU v5e 2x2 (no chip attached).

The TPU compiler refuses what interpret mode lets through: a ref kept in the
ANY memory space and read as a scalar, a lane block that is not a multiple
of 128, more scoped VMEM than the kernel asked for.  These tests lower and
compile the fused AG+GEMM and GEMM+RS kernels for the chip at smollm-360m's
TP=4 MLP widths and check that Mosaic emitted the kernel, and compile the
chat cell's whole serving step on one chip to check that decode attention
reads the stacked KV cache in place.

The topology is described only inside the module fixture (never at import):
one process at a time may load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import backend
from repro.configs import get_config
from repro.core.channels import BlockChannel
from repro.core.compiler import compile_overlap
from repro.models import lm
from repro.parallel.context import ParallelContext
from repro.serving import ServeEngine

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


# the chat cell (chipbench): smollm-360m in f32, 20 slots of 1536 rows,
# prefill chunks of 16 and decode blocks of 32, on one chip
SLOTS, MAX_LEN, CHUNK = 20, 1536, 16
PARENT_TEMP_BYTES = 2_403_254_784  # the step before decode attention took the kernel


def test_chat_step_reads_the_stacked_cache_in_place(mesh, monkeypatch):
    """The decode pass's attention is the decode-attention kernel, inside the
    pass's layer loop; it reads the stacked f32 cache
    ``[32, 20, 5, 1536, 64]`` through a bitcast, so nothing copies,
    transposes or converts the stack in either orientation or stages a
    layer's K and V, and the temporaries stay within the step's before.
    (The mixed pass's bf16 copy of the stack, once a step, is another
    matter and stays: at most two converts to bf16.)"""
    monkeypatch.setenv("REPRO_BACKEND", "tpu")
    one = Mesh(np.asarray(mesh.devices).reshape(-1)[:1].reshape(1, 1, 1),
               ("pod", "data", "model"))
    pc = ParallelContext(mesh=one, mode="overlap")
    cfg = get_config("smollm-360m")
    engine = ServeEngine.__new__(ServeEngine)  # the step only, with no arrays
    engine.cfg, engine.pc, engine.decode_block = cfg, pc, 32
    engine.stats = {"step_traces": 0}

    def shapes(tree, specs):
        return jax.tree_util.tree_map(
            lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=NamedSharding(one, sp)),
            tree, specs, is_leaf=lambda v: isinstance(v, P))

    params = shapes(jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg, pc,
                                                   jnp.float32)), lm.specs(cfg, pc))
    caches = shapes(jax.eval_shape(lambda: lm.init_caches(cfg, pc, SLOTS, MAX_LEN,
                                                          jnp.float32)),
                    lm.cache_specs(cfg, pc))

    def host(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(one, P()))

    n = (SLOTS,)
    inputs = (host(n), host((SLOTS, CHUNK)), host(n), host(n, jnp.bool_), host(n),
              host(n), host(n, jnp.float32), host(n), host(n), host(n), host(()))
    compiled = jax.jit(engine._build_step(), donate_argnums=(1,)).lower(
        params, caches, *inputs).compile()
    text = compiled.as_text()

    calls = [ln for ln in text.splitlines() if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert calls and all("decode_pass/layers/while/body" in ln for ln in calls)
    stack = r"f32\[32,20,5,(1536,64|64,1536)\]"
    moved = re.findall(stack + r"\{[^}]*\} (copy|transpose|convert)\(", text)
    assert not moved, moved
    assert not re.search(r"f32\[20,5,(1536,64|64,1536)\]", text)  # a layer staged
    assert len(re.findall(r"bf16\[32,20,5,1536,64\]\{[^}]*\} convert\(", text)) <= 2
    assert compiled.memory_analysis().temp_size_in_bytes <= PARENT_TEMP_BYTES
