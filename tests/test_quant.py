"""QuantSpec layer: wire-dtype split, dequant-GEMM, verifier + cache contract.

Covers the quantized-flows surface end to end:

  * spec validation and the encode/decode roundtrip bounds (per_tile and
    per_channel granularity);
  * the gradient-compression dedupe (training.compression re-exports the
    repro.core.quant int8 codec — one codepath, same semantics);
  * the headline property: with per-tile scales, end-to-end quant error
    through the ring is bounded independently of the world size (AG tiles
    are encoded ONCE at their origin, not per hop);
  * bitwise parity of the float wire paths with the pre-quant default;
  * weight-only dequant-GEMM (PackedWeight through blocked_dot) parity;
  * the verifier's quant checks (scale-table coverage / wire dtype /
    granularity) and the tune-cache v3 -> v4 migration (old records re-tune).
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis import PlanTables, PlanVerificationError, verify_tables
from repro.compat import make_mesh, shard_map
from repro.core.channels import BlockChannel
from repro.core.comp_tiles import blocked_dot
from repro.core.compiler import compile_overlap
from repro.core.plan import build_plan
from repro.core.quant import (
    PackedWeight,
    QuantSpec,
    WirePayload,
    decode_tree,
    dequantize,
    dequantize_int8,
    encode_tree,
    pack_weight,
    quantize,
    quantize_int8,
    wire_itemsize,
)

# NOTE: the hypothesis-driven forms of the roundtrip/world-independence
# properties live in tests/test_properties.py (which importorskips
# hypothesis); the parametrized versions here always run.


# ---- spec validation --------------------------------------------------------


def test_spec_validation():
    QuantSpec()  # default: inherit accum dtype
    QuantSpec(wire_dtype="int8", granularity="per_channel")
    QuantSpec(weight_dtype="int4", zero_point=True)
    with pytest.raises(ValueError, match="wire_dtype"):
        QuantSpec(wire_dtype="int4")  # int4 is weight-only, not a wire
    with pytest.raises(ValueError, match="granularity"):
        QuantSpec(granularity="per_row")
    with pytest.raises(ValueError, match="weight_dtype"):
        QuantSpec(weight_dtype="float16")
    with pytest.raises(ValueError, match="zero_point"):
        QuantSpec(zero_point=True)


def test_spec_identity_and_resolution():
    spec = QuantSpec()
    assert spec.resolve_wire("float32") == "float32"
    assert spec.is_identity("float32") and spec.is_identity("bfloat16")
    assert not spec.is_quantized
    q = QuantSpec(wire_dtype="int8")
    assert q.is_quantized and not q.is_identity("float32")
    assert QuantSpec(wire_dtype="bfloat16").is_identity("bfloat16")
    assert not QuantSpec(wire_dtype="bfloat16").is_identity("float32")
    assert wire_itemsize("int8") == 1 and wire_itemsize("bfloat16") == 2


def test_scale_slots_by_flow():
    q = QuantSpec(wire_dtype="int8")
    assert QuantSpec().scale_slots("ag", 8, 2, 8) == 0  # identity wire
    assert q.scale_slots("ag", 8, 2, 8) == 16  # once per origin tile
    assert q.scale_slots("rs", 8, 2, 8) == 14  # re-encoded per send edge
    assert q.scale_slots("ag_rs", 8, 2, 8) == 30  # tiles + flowing reduction
    with pytest.raises(ValueError, match="flow"):
        q.scale_slots("sideways", 8, 2, 8)


# ---- roundtrip bounds -------------------------------------------------------


@pytest.mark.parametrize("seed,scale", [(0, 1.0), (7, 1e-3), (42, 1e3)])
@pytest.mark.parametrize("granularity", ["per_tile", "per_channel"])
def test_quantize_roundtrip_bound(seed, scale, granularity):
    """|x - deq(quant(x))| <= scale/2 elementwise (symmetric absmax, no clip
    truncation: absmax maps exactly to the +/-127 endpoint)."""
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(16, 24) * scale, jnp.float32)
    payload = quantize(x, "int8", granularity)
    deq = dequantize(payload, jnp.float32)
    bound = 0.5 * np.asarray(payload.scale, np.float32)  # per-elem max error
    err = np.abs(np.asarray(deq) - np.asarray(x))
    assert (err <= bound + 1e-6).all()
    if granularity == "per_channel":
        assert payload.scale.shape == (x.shape[-1],)
    else:
        assert payload.scale.shape == ()


def test_per_channel_beats_per_tile_on_skewed_columns():
    rng = np.random.RandomState(0)
    x = rng.randn(64, 8).astype(np.float32)
    x[:, 0] *= 1000.0  # one hot column blows up the shared per-tile scale
    xt = jnp.asarray(x)
    err_tile = np.abs(np.asarray(
        dequantize(quantize(xt, "int8", "per_tile"), jnp.float32)) - x)
    err_chan = np.abs(np.asarray(
        dequantize(quantize(xt, "int8", "per_channel"), jnp.float32)) - x)
    assert err_chan[:, 1:].max() < err_tile[:, 1:].max() / 10.0


def test_encode_tree_passthrough_and_identity():
    spec = QuantSpec(wire_dtype="int8")
    x = jnp.ones((4, 4), jnp.float32)
    ids = jnp.arange(4, dtype=jnp.int32)  # routing tables ride untouched
    enc = encode_tree({"x": x, "ids": ids}, spec, "float32")
    assert isinstance(enc["x"], WirePayload)
    assert enc["ids"] is ids
    dec = decode_tree(enc, spec, "float32")
    assert dec["x"].dtype == jnp.float32 and dec["ids"] is ids
    # identity spec: encode/decode return the SAME objects (bitwise path)
    ident = encode_tree({"x": x}, QuantSpec(), "float32")
    assert ident["x"] is x


# ---- compression dedupe -----------------------------------------------------


def test_compression_reexports_shared_codec():
    from repro.training import compression

    assert compression.quantize_int8 is quantize_int8
    assert compression.dequantize_int8 is dequantize_int8
    g = jnp.asarray(np.random.RandomState(3).randn(33, 7), jnp.float32)
    q, scale = quantize_int8(g)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    err = np.abs(np.asarray(dequantize_int8(q, scale)) - np.asarray(g))
    assert err.max() <= float(scale) * 0.5 + 1e-7
    # error feedback still closes over the shared codec
    q2, s2, new_err = compression.compress_with_feedback(g, jnp.zeros_like(g))
    np.testing.assert_allclose(
        np.asarray(dequantize_int8(q2, s2) + new_err), np.asarray(g),
        rtol=1e-6, atol=1e-6)


# ---- world-size independence (the wire-edge property) -----------------------


@pytest.mark.parametrize("seed", [3, 17])
@pytest.mark.parametrize("world", [1, 2, 4, 8])
def test_ag_quant_error_independent_of_world(seed, world):
    """AG tiles are encoded once at their origin, so the end-to-end error of
    gather -> dequant -> GEMM is bounded by a constant that does NOT grow
    with the world size (each shard's scale <= the global absmax scale)."""
    rng = np.random.RandomState(seed)
    m, k, n = 32, 16, 8
    x = rng.randn(world * m, k).astype(np.float32)
    w = rng.randn(k, n).astype(np.float32)
    shards = np.split(x, world, axis=0)
    deq = np.concatenate([
        np.asarray(dequantize(quantize(jnp.asarray(s), "int8"), jnp.float32))
        for s in shards])
    err = np.abs(deq @ w - x @ w).max()
    # world-independent bound: elementwise quant error <= global_absmax/254,
    # one GEMM row contracts k of them against |w|
    bound = k * (np.abs(x).max() / 254.0 + 1e-6) * np.abs(w).max()
    assert err <= bound


# ---- mesh parity + bitwise float paths --------------------------------------


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh((4,), ("model",))


def _run(mesh, fn, *args):
    f = shard_map(fn, mesh, in_specs=(P(None, None),) * len(args),
                  out_specs=P("model", None), check_rep=False,
                  axis_names={"model"})
    return f(*args)


@pytest.mark.parametrize("kind", ["matmul_rs", "ag_matmul"])
def test_int8_flow_parity_on_mesh(mesh4, kind):
    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.randn(64, 64), jnp.float32)
    w = jnp.asarray(rng.randn(64, 32), jnp.float32)
    ch = BlockChannel(axis="model")
    y_f = _run(mesh4, compile_overlap(kind, ch), x, w)
    y_q = _run(mesh4, compile_overlap(
        kind, ch.with_(quant=QuantSpec(wire_dtype="int8"))), x, w)
    rel = float(jnp.linalg.norm(y_q - y_f) / jnp.linalg.norm(y_f))
    assert rel < 0.05, rel


@pytest.mark.parametrize("wire", ["float32", None])
def test_float_wire_is_bitwise_identical(mesh4, wire):
    """The fp32 flow path must not change AT ALL under the refactor: a
    float32 wire over a float32 accum is encode/decode identity."""
    rng = np.random.RandomState(12)
    x = jnp.asarray(rng.randn(64, 64), jnp.float32)
    w = jnp.asarray(rng.randn(64, 32), jnp.float32)
    ch = BlockChannel(axis="model")
    y_def = _run(mesh4, compile_overlap("matmul_rs", ch), x, w)
    quant = None if wire is None else QuantSpec(wire_dtype=wire)
    ch_q = ch if quant is None else ch.with_(quant=quant)
    y_q = _run(mesh4, compile_overlap("matmul_rs", ch_q), x, w)
    assert bool(jnp.all(y_def == y_q))


def test_context_quant_threading(mesh4):
    from repro.parallel.context import ParallelContext

    pc = ParallelContext(mesh=mesh4, dp_axes=(),
                         quant=QuantSpec(wire_dtype="int8"))
    assert pc.channel.quant.wire_dtype == "int8"
    assert ParallelContext(mesh=mesh4, dp_axes=(), quant=True).quant == "auto"
    with pytest.raises(ValueError, match="quant"):
        ParallelContext(mesh=mesh4, dp_axes=(), quant="int8")


# ---- weight-only dequant-GEMM ----------------------------------------------


@pytest.mark.parametrize("wdtype,zp", [("int8", False), ("int4", True)])
def test_packed_blocked_dot_parity(wdtype, zp):
    rng = np.random.RandomState(21)
    x = jnp.asarray(rng.randn(32, 48), jnp.float32)
    w = jnp.asarray(rng.randn(48, 64), jnp.float32)
    packed = pack_weight(w, QuantSpec(weight_dtype=wdtype, zero_point=zp))
    assert isinstance(packed, PackedWeight)
    from repro.core.quant import dequantize_weight

    w_ref = dequantize_weight(packed.q, packed.scale, packed.zero)
    ref = x @ w_ref
    for unroll in (False, True):
        got = blocked_dot(x, packed, (16, 32, 16), accum=jnp.float32,
                          unroll=unroll)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    # col_slice keeps scales aligned with the sliced codes
    lo, hi = 16, 48
    sliced = packed.col_slice(lo, hi)
    got = blocked_dot(x, sliced, (16, 32, 16), accum=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref[:, lo:hi]),
                               rtol=2e-5, atol=2e-5)


# ---- verifier quant checks --------------------------------------------------


def _quant_tables(kind="matmul_rs", world=8, nch=2):
    ch = BlockChannel(axis="model", quant=QuantSpec(wire_dtype="int8"))
    plan = build_plan(kind, ch, world, nch)
    return PlanTables.from_plan(plan)


def test_verifier_accepts_quant_plan():
    tables = _quant_tables()
    report = verify_tables(tables)
    assert report.checks > 0
    assert tables.wire_dtype == "int8" and tables.scale_slots is not None


@pytest.mark.parametrize("field,value,check", [
    ("scale_slots", 3, "quant_scale_slots"),
    ("wire_dtype", "int4", "quant_wire_dtype"),
    ("granularity", "per_row", "quant_granularity"),
])
def test_verifier_flags_quant_mutations(field, value, check):
    tables = dataclasses.replace(_quant_tables(), **{field: value})
    with pytest.raises(PlanVerificationError) as e:
        verify_tables(tables)
    assert e.value.check == check


def test_verifier_skips_unquantified_tables():
    ch = BlockChannel(axis="model")
    tables = PlanTables.from_plan(build_plan("matmul_rs", ch, 8, 2))
    assert tables.scale_slots == 0  # identity wire allocates no scale table
    verify_tables(tables)  # and the quant pass stays green


# ---- tune-cache schema migration --------------------------------------------


def test_cache_v3_records_retune():
    from repro.tune import CACHE_SCHEMA, _parse_record

    assert CACHE_SCHEMA == 4
    v4 = {
        "schema": 4, "order": "ring", "num_channels": 2,
        "accum_dtype": "float32", "comp_tile": [64, 128, 128],
        "flow": "int8", "ranker": "model", "score": 1.0,
    }
    parsed = _parse_record(v4)
    assert parsed is not None and parsed["candidate"].flow == "int8"
    v3 = dict(v4, schema=3)
    v3.pop("flow")
    assert _parse_record(v3) is None  # pre-quant schema: silent re-tune
    assert _parse_record(dict(v4, flow="int4")) is None  # junk flow


def test_autotune_explores_flow_axis(tmp_path, mesh4, monkeypatch):
    """Tuning over the quant-widened space must consider int8 wires and
    record the winner's flow in a schema-4 entry."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
    from repro.tune import autotune
    from repro.tune.candidates import QUANT_SPACE

    # comm-bound: tiny k keeps compute cheap while m*n rides the wire
    result = autotune("matmul_rs", signature=(1, 512, 64, 2048), world=4,
                      mesh=mesh4, ranker="model", space=QUANT_SPACE)
    assert result.channel.quant is not None
    records = list(tmp_path.rglob("*.json*"))
    assert records, "autotune must persist a cache entry"


# comm-bound GEMM signatures at world 4: narrow contraction per byte moved,
# so the wire gates the pipeline.  matmul_rs sigs are (lead, m_glob, k_loc,
# n); ag_matmul sigs are (lead, m_loc, k, n_loc).
COMM_BOUND = {
    "rs-long-seq": ("matmul_rs", (1, 2048, 32, 2048)),
    "rs-wide-out": ("matmul_rs", (1, 1024, 128, 4096)),
    "ag-deep-k": ("ag_matmul", (1, 512, 4096, 512)),
}


@pytest.mark.parametrize("name", sorted(COMM_BOUND))
def test_int8_wire_beats_full_precision_modeled(name):
    """On a comm-bound shape the best int8-wire candidate beats the best
    full-precision one on the modeled scale: the int8 wire quarters the
    bytes on the wire at a fixed scale-table overhead."""
    from repro.tune import cost
    from repro.tune.candidates import QUANT_SPACE, enumerate_candidates

    kind, sig = COMM_BOUND[name]
    cands = enumerate_candidates(kind, space=QUANT_SPACE, sig=sig, world=4)

    def best(flow):
        return min(cost.predict_cost(kind, sig, 4, c) for c in cands if c.flow == flow)

    assert best("int8") < best(None)


def test_schema3_record_retunes_into_schema4(tmp_path):
    """A stored schema-3 record (no flow axis) is re-tuned, not reused, and
    rewritten as a schema-4 entry that carries the winner's flow — int8 on a
    comm-bound shape."""
    from repro.tune import CACHE_SCHEMA, _entry_key, autotune
    from repro.tune import cache as tune_cache
    from repro.tune.candidates import QUANT_SPACE

    kind, sig = COMM_BOUND["rs-long-seq"]
    fp = tune_cache.mesh_fingerprint(None, axis="model", world=4)
    key = _entry_key(kind, "model", 4, sig, QUANT_SPACE)
    v3 = {"schema": 3, "kind": kind, "signature": list(sig), "world": 4,
          "order": "ring", "num_channels": 1, "accum_dtype": "float32",
          "comp_tile": [64, 128, 128], "ranker": "model", "score": 1.0}
    tune_cache.store_entry(fp, key, v3, directory=str(tmp_path))
    res = autotune(kind, signature=sig, world=4, ranker="model",
                   space=QUANT_SPACE, cache_dir=str(tmp_path))
    assert not res.cache_hit and res.candidate.flow == "int8"
    tune_cache.clear_memo()
    rec = tune_cache.load_entry(fp, key, directory=str(tmp_path))
    assert rec["schema"] == CACHE_SCHEMA and rec["flow"] == "int8"

