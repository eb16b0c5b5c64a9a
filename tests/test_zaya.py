"""ZAYA1 through the normal path at a small size, held to the float32
reference of the benchmark's family file (``benchmark/lib/families/zaya.py``,
which imports nothing of the program, runs every position through the
equations with no cache, no tails and no scan, and the experts as a plain
loop): hidden 64, three layers, 4 heads over 2 of 16, a router of 16
columns over 4 experts of 32 and the skip. Logits are compared, not tokens.

The seeded checkpoint fills every bias, ``gamma`` and ``beta`` with zeros
and every norm, temperature and scale with ones; :class:`Stirred` draws
them from the seed instead, so that each of them matters to the logits.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from demodel_tpu.models import zaya
from demodel_tpu.models.common import attend
from demodel_tpu.serve import GenEngine, kvcache
from demodel_tpu.serve.scheduler import _Seq
from demodel_tpu.utils.metrics import HUB, labeled
from tests.test_exaone_moe import _engine_logits

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))

from lib import checkpoint, families, reference  # noqa: E402

SMALL = {
    "model_type": "zaya", "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "num_experts": 4, "num_experts_per_tok": 1,
    "moe_intermediate_size": 32, "router_hidden_size": 16, "cca_time0": 2,
    "cca_time1": 2, "partial_rotary_factor": 0.5,
    "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5,
                                   "rope_theta": 10000,
                                   "rope_type": "default"},
                        "rope_type": "default"},
    "layer_types": ["hybrid"] * 5, "hidden_act": "silu",
    "attention_bias": False, "sliding_window": None, "lm_head_bias": False,
    "tie_word_embeddings": True, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 131072, "torch_dtype": "bfloat16",
}
SEED = 2147483801
ENGINE = dict(max_batch=4, queue_limit=8, max_new_tokens=24, kv_mb=1)
FAMILY = families.of(SMALL)


class Stirred:
    """A seeded checkpoint whose ones and zeros are drawn from the seed:
    ones become 1 + N(0, 0.2), zeros N(0, 0.3) (``beta`` N(0, 0.02), or one
    expert would take every token). The reference reads it as it reads any
    checkpoint (``config``, ``tensor``)."""

    def __init__(self, ckpt):
        self.config, self.tensors, self._ckpt = ckpt.config, ckpt.tensors, ckpt

    def tensor(self, name: str):
        a, t = self._ckpt.tensor(name), self._ckpt.tensors[name]
        if t.fill == "normal":
            return a
        # beta at the scale of the differences it is added to
        scale = 0.02 if name.endswith("balancing_bias") \
            else 0.2 if t.fill == "ones" else 0.3
        noise = np.random.default_rng([SEED, t.index]).normal(
            0, scale, a.shape)
        return (a.astype(np.float32) + noise).astype(ml_dtypes.bfloat16)


def _params(ckpt, model: dict, mesh=None):
    cfg = zaya.ZayaConfig.from_hf(model)
    weights = {name: jnp.asarray(ckpt.tensor(name)) for name in ckpt.tensors}
    params = zaya.load_params(weights, cfg, mesh=mesh)
    assert not weights, sorted(weights)     # the loader took every tensor
    return params, cfg


@pytest.fixture(scope="module")
def small():
    ckpt = checkpoint.Checkpoint(SMALL, SEED, n_shards=2)
    return (ckpt, *_params(ckpt, SMALL))


@pytest.fixture(scope="module")
def stirred():
    ckpt = Stirred(checkpoint.Checkpoint(SMALL, SEED, n_shards=2))
    return (ckpt, *_params(ckpt, SMALL))


def _float32(params, cfg):
    return (jax.tree.map(lambda a: a.astype(jnp.float32), params),
            dataclasses.replace(cfg, dtype="float32"))


def _prompts(lengths) -> list[list[int]]:
    rng = np.random.default_rng([SEED, 7])
    return [[int(t) for t in rng.integers(0, SMALL["vocab_size"], n)]
            for n in lengths]


def _served(ckpt, params, cfg, lengths=(40, 17, 9), steps=12,
            block_tokens=4):
    """What the engine's two programs give for prompts of ``lengths`` (each
    prefilled into a lease: pages and tails) and ``steps`` steps of their
    ragged batch (through the one-array page and the tails, teacher-forced
    with each step's own first choice), beside the float32 reference's
    logits for the same sequences."""
    engine = GenEngine(params, cfg, block_tokens=block_tokens, **ENGINE)
    prompts = _prompts(lengths)
    try:
        got = _engine_logits(engine, prompts, steps=steps)
    finally:
        engine.stop()
    assert engine.pool.describe()["in_use_blocks"] == 0
    seqs = [f for f, _lg in got]
    wanted = [range(len(p) - 1, len(f)) for p, (f, _lg) in zip(prompts, got)]
    ref = reference.logits(ckpt, seqs, wanted)
    return got, wanted, [np.asarray(r)[:len(w)]
                         for r, w in zip(ref, wanted)], (ckpt, seqs)


@pytest.mark.parametrize("lengths,block_tokens,steps,dense", [
    ((40, 17, 9), 4, 12, 2048),     # a table of two tiles: the rectangle
    ((40, 17, 9), 4, 12, 0),        # the same, every program routing
    ((40, 17, 9), 4, 12, 1 << 20),  # and none
    ((1, 2, 3), 4, 6, 2048),    # the tails and the value shift at the start
    ((70, 33, 5), 2, 12, 2048),     # past two tiles: the filled tiles
    ((530, 40, 3), 16, 4, 2048),    # past 512 positions at blocks of 16
], ids=["inside-two-tiles", "every-program-routes", "no-program-routes",
        "prompts-of-1-2-3", "past-two-tiles", "past-512-positions"])
def test_float32_program_is_the_reference(stirred, monkeypatch, lengths,
                                          block_tokens, steps, dense):
    """The same weights computed in float32 by the program: a prompt's two
    convolutions, its values shifted by a token, pages and tails written;
    then steps of rows of unequal length, each reading its tails and the
    one-array page under zero-padded queries, the layers under one scan
    with the router's stream carried through it, against the reference's
    plain pass over every position. Every bias, ``gamma``, ``beta``,
    temperature and merge vector is stirred. A step's few rows go through
    every expert of the layer and a prompt's are routed (``zaya.DENSE``,
    2 048 rows x experts: here prompts past 512 tokens route), so the two
    ways are also forced on every program. No rounding to hide behind:
    2e-4 on logits of order 1 (float32 sums in another order)."""
    ckpt, params, cfg = stirred
    monkeypatch.setattr(zaya, "DENSE", dense)
    got, _wanted, ref, _ = _served(ckpt, *_float32(params, cfg),
                                   lengths=lengths, steps=steps,
                                   block_tokens=block_tokens)
    for (_fed, lg), r in zip(got, ref):
        np.testing.assert_allclose(lg, r, rtol=0, atol=2e-4)


class TestAgainstTheReference:
    """The bfloat16 program, prefill then decode through pages and tails,
    against the family's float32 ``logits``, on the seeded fills the
    benchmark runs. Rounding alone moves a row's logits by hundredths;
    one expert a token means that a choice exchanged at a near-tie (the
    program's router reads the residual as bfloat16 holds it) moves the
    whole of the sublayer's sum and, at the seeded router's ``p`` near 1,
    most of the row with it, so at most a tenth of the rows may lie more
    than 0.5 out, however far. By the median row the program
    lies no further from the reference than twice what the reference's own
    ``bfloat16`` mode does, and the int8 mode lies further than both."""

    @pytest.fixture(scope="class")
    def served(self, small):
        return _served(*small, lengths=(40, 17, 9, 30), steps=24)

    def test_logits_agree(self, served):
        got, _wanted, ref, _ = served
        apart = np.concatenate([np.abs(lg - r).max(axis=1)
                                for (_f, lg), r in zip(got, ref)])
        assert np.median(apart) < 0.05, np.median(apart)
        assert (apart > 0.5).mean() <= 0.1, apart

    def test_precision_below_fails_where_bfloat16_passes(self, served):
        got, wanted, ref, (ckpt, seqs) = served

        def mode(name) -> list:
            low = reference.logits(ckpt, seqs, wanted, mode=name)
            return [np.asarray(lo)[:len(w)] for lo, w in zip(low, wanted)]

        def apart(rows) -> float:       # the median row's widest logit
            return float(np.median(np.concatenate(
                [np.abs(a - r).max(axis=1) for a, r in zip(rows, ref)])))

        program = [lg for _fed, lg in got]
        sound, control = mode("bfloat16"), mode("int8")
        assert apart(program) <= 2 * apart(sound), (apart(program),
                                                    apart(sound))
        assert apart(control) > 1.5 * max(apart(sound), apart(program)), (
            apart(control), apart(sound), apart(program))


# ----------------------------------------------------- the layer's wiring


def _layer_weights(params, cfg, li: int) -> dict:
    w = jax.tree.map(lambda a: a[li], params["layers"])
    return {**w, **zaya.unpack(w["vectors"], cfg)}


def test_padded_queries_over_one_array_are_attend_with_k_and_v_apart():
    """The step's form against the prompt's, to the bit in float32: 8
    query rows as wide as the page, head ``i`` zero outside the columns of
    its key head, over ``[v | k^]`` as one cached head whose first columns
    are the values, keeping its own head's values of what comes out; and
    grouped attention of the same queries over the same keys and values
    as two heads."""
    B, T, H, Hkv, hd = 2, 5, 8, 2, 16
    keys = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(keys[0], (B, T, H, hd), jnp.float32)
    k = jax.random.normal(keys[1], (B, T, Hkv, hd), jnp.float32)
    v = jax.random.normal(keys[2], (B, T, Hkv, hd), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    apart = attend(q, k, v, positions, scale=hd ** -0.5)
    page = jnp.concatenate([v.reshape(B, T, Hkv * hd),
                            k.reshape(B, T, Hkv * hd)], -1)[:, :, None]
    wide = jnp.einsum("btjgd,jk->btjgkd", q.reshape(B, T, Hkv, H // Hkv, hd),
                      jnp.eye(Hkv)).reshape(B, T, H, Hkv * hd)
    wide = jnp.concatenate([jnp.zeros_like(wide), wide], axis=-1)
    o = attend(wide, page, page[..., :Hkv * hd], positions,
               scale=hd ** -0.5).reshape(B, T, Hkv, H // Hkv, Hkv, hd)
    own = jnp.stack([o[:, :, j, :, j] for j in range(Hkv)], axis=2)
    np.testing.assert_array_equal(np.asarray(own.reshape(B, T, H * hd)),
                                  np.asarray(apart))


def test_the_scan_is_the_unrolled_stack(stirred):
    """One ``lax.scan`` over the stacked weights against a plain loop over
    the layers calling the same ``_layer`` with a Python index: the same
    logits, pages, tails and counts in float32."""
    _ckpt, params, cfg = stirred
    params, cfg = _float32(params, cfg)
    tokens = jnp.asarray(_prompts((12,)), jnp.int32)
    logits, written, counts, skips, _moved = zaya.step_prefill(
        params, tokens, cfg)
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    carry = (params["embed"][tokens],
             jnp.zeros((B * T, cfg.router_hidden_size), jnp.float32))
    stacks = (params["experts_gate_up"], params["experts_down"])
    outs = []
    for li in range(cfg.num_hidden_layers):
        carry, out = zaya._layer(
            carry, (jax.tree.map(lambda a: a[li], params["layers"]),
                    jnp.int32(li), None), cfg, stacks, positions,
            zaya._turns(positions, cfg), jnp.ones((B, T), bool),
            lambda _li: None, None)
        outs.append(out)
    np.testing.assert_allclose(
        zaya._head(params, carry[0][:, -1], cfg), logits, rtol=0, atol=1e-5)
    pages, tails = written.kv, written.state["tail"].new
    for li, (page, tail, n) in enumerate(outs):
        np.testing.assert_allclose(page, pages[li], rtol=0, atol=1e-5)
        np.testing.assert_allclose(tail, tails[li], rtol=0, atol=1e-5)
        np.testing.assert_array_equal(n[:-1], counts[li])
        assert int(n[-1]) == int(skips[li])
    assert int(counts.sum() + skips.sum()) == T * cfg.num_hidden_layers


def test_a_token_routed_to_the_skip_gets_the_residual_alone(stirred,
                                                            monkeypatch):
    """The router's last output is nobody's expert: such a token's sum is
    zero to the bit, it is counted a skip and lands on no expert, and the
    sublayer leaves it the merge of its residual with nothing."""
    _ckpt, params, cfg = stirred
    params, cfg = _float32(params, cfg)
    w = _layer_weights(params, cfg, 1)
    stacks = (params["experts_gate_up"], params["experts_down"])
    g = jax.random.normal(jax.random.key(5), (64, cfg.hidden_size))
    rho = jnp.zeros((64, cfg.router_hidden_size), jnp.float32)
    live = jnp.ones((64,), bool)
    _rho, chosen, _p = zaya.route(w, g, rho, cfg)
    skipped = np.asarray(chosen) == cfg.num_experts
    assert 0 < skipped.sum() < 64
    for dense in (zaya.DENSE, 0):   # every expert for every row; routed
        monkeypatch.setattr(zaya, "DENSE", dense)
        y, _rho, counts = zaya._moe(w, stacks, jnp.int32(1), g, rho, live,
                                    cfg, None)
        assert not np.asarray(y)[skipped].any()
        assert np.abs(np.asarray(y)[~skipped]).max(axis=1).min() > 0
        np.testing.assert_array_equal(
            counts, np.bincount(np.asarray(chosen),
                                minlength=cfg.num_experts + 1))
    # a bias towards the skip sends every token there, and the layer's
    # result is then the merge of the residual with zeros
    named = zaya.unpack(params["layers"]["vectors"], cfg)
    named["router_bias"] = named["router_bias"].at[:, -1].set(10.0)
    biased = {**params, "layers": {**params["layers"],
                                   "vectors": zaya.pack(named, cfg)}}
    tokens_in = jnp.asarray(_prompts((9,)), jnp.int32)
    _lg, _written, counts, skips, _moved = zaya.step_prefill(
        biased, tokens_in, cfg)
    assert not np.asarray(counts).any()
    assert np.asarray(skips).tolist() == [9] * cfg.num_hidden_layers
    x = jax.random.normal(jax.random.key(6), (1, 9, cfg.hidden_size))
    wb = _layer_weights(biased, cfg, 1)
    positions = jnp.arange(9)[None]
    turns = zaya._turns(positions, cfg)
    (out, _rho), _ys = zaya._layer(
        (x, jnp.zeros((9, cfg.router_hidden_size))),
        (jax.tree.map(lambda a: a[1], biased["layers"]), jnp.int32(1), None),
        cfg, stacks, positions, turns, jnp.ones((1, 9), bool),
        lambda _li: None, None)
    a, _page, _tail = zaya._attention(
        wb, zaya._norm(x, wb["attn_norm"], cfg), cfg, positions, turns,
        None, None)
    x1 = zaya._merge(wb, "attn_merge", x, a)
    np.testing.assert_allclose(
        out, zaya._merge(wb, "mlp_merge", x1, jnp.zeros_like(x1)),
        rtol=0, atol=1e-6)


def test_the_routers_stream_of_a_layer_enters_the_next(stirred):
    """``rho`` of layer ``l`` is the down-projection plus ``gamma`` times
    the layer before's, and what the scan carries into layer ``l + 1``."""
    _ckpt, params, cfg = stirred
    params, cfg = _float32(params, cfg)
    g = jax.random.normal(jax.random.key(8), (10, cfg.hidden_size))
    w0, w1 = (_layer_weights(params, cfg, li) for li in (0, 1))
    rho0, _c, _p = zaya.route(w0, g, jnp.zeros((10, 16)), cfg)
    np.testing.assert_allclose(rho0, g @ w0["router_down"], atol=1e-5)
    rho1, chosen, _p = zaya.route(w1, g, rho0, cfg)
    assert np.abs(np.asarray(w1["router_gamma"])).min() > 0
    np.testing.assert_allclose(
        rho1, g @ w1["router_down"] + w1["router_gamma"] * rho0, atol=1e-5)
    alone, chosen_alone, _p = zaya.route(w1, g, jnp.zeros((10, 16)), cfg)
    assert np.abs(np.asarray(rho1 - alone)).max() > 0.05
    # and in the stack: without layer 0's stream, layer 1 and 2 choose
    # otherwise somewhere in a prompt
    tokens = jnp.asarray(_prompts((64,)), jnp.int32)
    _lg, _w, counts, _skips, _m = zaya.step_prefill(params, tokens, cfg)
    named = zaya.unpack(params["layers"]["vectors"], cfg)
    named["router_gamma"] = jnp.zeros_like(named["router_gamma"])
    cut = {**params, "layers": {**params["layers"],
                                "vectors": zaya.pack(named, cfg)}}
    _lg, _w, without, _skips, _m = zaya.step_prefill(cut, tokens, cfg)
    np.testing.assert_array_equal(counts[0], without[0])
    assert (np.asarray(counts[1:]) != np.asarray(without[1:])).any()


def test_the_cut_is_the_first_layers_the_norm_and_the_table():
    """The benchmark cuts the depth alone: a checkpoint of the first three
    layers of five is, tensor for tensor, the uncut one's first three, its
    norm and its table, and the program built from it is the uncut
    program's first three layers and the same norm and head."""
    whole = dict(SMALL, num_hidden_layers=5)
    full = checkpoint.Checkpoint(whole, SEED, n_shards=2)
    part = checkpoint.Checkpoint(SMALL, SEED, n_shards=2)
    assert set(part.tensors) < set(full.tensors)
    for name in part.tensors:
        np.testing.assert_array_equal(
            part.tensor(name).view(np.uint16),
            full.tensor(name).view(np.uint16), err_msg=name)
    (p5, c5), (p3, c3) = _params(full, whole), _params(part, SMALL)
    assert (c5.num_hidden_layers, c3.num_hidden_layers) == (5, 3)
    E = c3.num_experts
    first = {**p5, "layers": jax.tree.map(lambda a: a[:3], p5["layers"]),
             "experts_gate_up": p5["experts_gate_up"][:3 * E],
             "experts_down": p5["experts_down"][:3 * E]}
    assert jax.tree.structure(first) == jax.tree.structure(p3)
    for a, b in zip(jax.tree.leaves(first), jax.tree.leaves(p3)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# ------------------------------------------------- the page and the tails


def test_every_layer_pages_and_keeps_a_tail(small):
    """The module states a page of one array for every layer and one row
    of tails a layer in the sequence's slot; the pool's bytes follow."""
    _ckpt, _params_, cfg = small
    spec = zaya.cache_spec(cfg)
    assert spec == kvcache.CacheSpec(
        3, 1, 64, values=32, state=(("tail", (3, 2 * 96 + 16), "bfloat16"),))
    pool = kvcache.KVBlockPool(spec, slots=4, block_tokens=4, budget_mb=1,
                               dtype="bfloat16")
    assert pool.block_bytes == 3 * 4 * 64 * 2       # one array, once
    assert pool.slot_bytes == 3 * 208 * 2
    assert len(pool.arrays) == 2 and pool.v is None
    assert pool.state["tail"].shape == (3, 5, 208)
    # the published widths: 512 columns a position a layer, the same bytes
    # as keys and values apart; 2 688 columns of tails
    published = zaya.ZayaConfig(num_hidden_layers=16, dtype="bfloat16")
    spec = zaya.cache_spec(published)
    assert (spec.layers, spec.kv_heads, spec.head_dim, spec.values) \
        == (16, 1, 512, 256)
    assert spec.state == (("tail", (16, 2688), "bfloat16"),)
    big = kvcache.KVBlockPool(spec, slots=64, block_tokens=16, budget_mb=64,
                              dtype="bfloat16")
    assert big.block_bytes == 16 * 16384 and big.slot_bytes == 86016
    model = dict(SMALL, num_hidden_layers=16, num_attention_heads=8,
                 head_dim=128)
    assert FAMILY.position_bytes(model) == 16384
    assert FAMILY.tail_bytes(model) == 86016
    # general kernels: k0 - 1 rows of u, k1 - 1 of c0
    wide = dataclasses.replace(cfg, cca_time0=4, cca_time1=3)
    assert wide.tail_dim == 5 * 96 + 16


@pytest.mark.parametrize("k0,k1", [(1, 1), (3, 2), (2, 4)])
def test_other_kernels_keep_the_tails_they_need(k0, k1):
    """The module is written for any ``cca_time0`` and ``cca_time1``: a
    prompt then steps, in float32, against the reference at the same
    kernels (tails of ``k0 - 1`` and ``k1 - 1`` rows; none at 1)."""
    model = dict(SMALL, cca_time0=k0, cca_time1=k1, num_hidden_layers=2)
    ckpt = Stirred(checkpoint.Checkpoint(model, SEED, n_shards=2))
    params, cfg = _params(ckpt, model)
    got, _wanted, ref, _ = _served(ckpt, *_float32(params, cfg),
                                   lengths=(7, 2, 1), steps=6)
    for (_fed, lg), r in zip(got, ref):
        np.testing.assert_allclose(lg, r, rtol=0, atol=2e-4)


def test_a_pad_row_writes_the_scratch_block_and_slot_only(small):
    """One sequence in a bucket of four beside a bystander's lease: after a
    prefill and five steps only the sequence's own blocks and slot and the
    scratch block and slot have changed, in every layer."""
    _ckpt, params, cfg = small
    engine = GenEngine(params, cfg, block_tokens=4, **ENGINE)
    pool = engine.pool
    bystander = pool.alloc(2)
    pool.arrays = jax.jit(lambda *a: tuple(x + 3 for x in a),
                          out_shardings=pool.shardings)(*pool.arrays)
    before = [np.asarray(a, np.float32) for a in pool.arrays]
    prompt = _prompts((9,))[0]
    lease = pool.alloc(pool.blocks_for(len(prompt) + 5))
    _ids, (logits, *_s) = engine._prefill(prompt, lease)
    seq = _Seq(None, lease, len(prompt), int(np.asarray(logits)[0].argmax()))
    for _ in range(5):
        _w, sent = engine._decode_inputs([seq])
        ids, _out = pool.apply(engine._jdecode, engine.params,
                               jax.device_put(sent), engine._prev_ids)
        seq.length += 1
        seq.last_tok = int(np.asarray(ids)[0])
    pages, tails = (np.asarray(a, np.float32) for a in pool.arrays)
    mine = np.zeros(pages.shape[1], bool)
    mine[lease.blocks + [pool.scratch_block]] = True
    np.testing.assert_array_equal(pages[:, ~mine], before[0][:, ~mine])
    slots = np.zeros(tails.shape[1], bool)
    slots[[lease.slot, pool.scratch_slot]] = True
    np.testing.assert_array_equal(tails[:, ~slots], before[1][:, ~slots])
    for li in range(cfg.num_hidden_layers):
        assert (pages[li, lease.blocks[:3]]
                != before[0][li, lease.blocks[:3]]).any()
        assert (tails[li, lease.slot] != before[1][li, lease.slot]).any()
    lease.free()
    bystander.free()
    engine.stop()


# ------------------------------------------------------ served, and seen


def test_spans_and_counters_name_the_page_the_tails_and_the_skips(small):
    """``cca_kv_bytes``, ``state_bytes``, ``zero_tokens`` and
    ``assignments`` beside the experts' counts on the step's and the
    prefill's device span, through the module's ``observe``; the counters:
    an assignment is an expert's or the skip's, and nobody's is absent."""
    from demodel_tpu.utils import trace

    _ckpt, params, cfg = small
    before = HUB.snapshot()
    trace.reset()
    trace.enable()
    try:
        engine = GenEngine(params, cfg, block_tokens=4, **ENGINE).start()
        try:
            engine.generate(_prompts((20,))[0], 6, timeout=240)
        finally:
            engine.stop()
        spans = trace.buffer().snapshot()
    finally:
        trace.reset()
    position = 3 * 64 * 2           # three layers of [v 32 | k^ 32]
    tail = 3 * 208 * 2
    dev, = [s["attrs"] for s in spans if s["name"] == "serve.prefill-device"]
    assert dev["cca_kv_bytes"] == 20 * position
    assert dev["state_bytes"] == tail               # written once
    assert dev["assignments"] == 20 * 3
    steps = [s["attrs"] for s in spans if s["name"] == "serve.decode-step"]
    assert len(steps) == 5
    for i, a in enumerate(steps):
        assert a["cca_kv_bytes"] == (20 + i) * position
        assert a["state_bytes"] == 2 * tail         # read and written
        assert a["assignments"] == 3
        assert a["zero_tokens"] + a["expert_tokens"] == 3
        assert a["expert_rows"] == a["assignments"]
        assert a["kv_positions_in_place"] == 0      # the CPU keeps the loop
    seen = [dev, *steps]
    after = HUB.snapshot()

    def counted(name):
        return after[name] - before.get(name, 0)

    kinds = {held: counted(labeled("gen_moe_assignments_total", held=held))
             for held in ("true", "false", "zero")}
    assert kinds["zero"] == sum(a["zero_tokens"] for a in seen)
    assert kinds["true"] == sum(a["expert_tokens"] for a in seen)
    assert kinds["false"] == 0
    assert counted("gen_cca_kv_bytes_total") \
        == sum(a["cca_kv_bytes"] for a in seen)
    assert counted("gen_state_bytes_total") \
        == sum(a["state_bytes"] for a in seen)
    assert counted("gen_moe_experts_hit_total") \
        == sum(a["experts_hit"] for a in seen)
    # the benchmark's readers, on these spans
    obs = type("Obs", (), {
        "window_spans": lambda self, name: [{"attrs": a} for a in steps],
        "records": [], "t0": 0.0, "t1": 1.0, "model": SMALL})()
    share = families.of({"model_type": "longcat_flash"}).zero_share(
        obs, "serve.decode-step")
    assert share == pytest.approx(
        100.0 * sum(a["zero_tokens"] for a in steps) / 15)
    assert 0 < FAMILY.kv_share(obs, "serve.decode-step", "cca_kv_bytes") < 100


@pytest.mark.parametrize("rows,reads,dense", [
    (4, 0, True),       # a step: every expert of every layer, no kernel
    (512, None, False),     # a prompt: the grouped kernel's visits
], ids=["a-step-is-dense", "a-prompt-routes"])
def test_a_dense_program_counts_no_kernel_visits(rows, reads, dense):
    """``expert_reads`` is the grouped kernel's visits for this family as
    for every other: a program that computes every expert for every row
    (``rows x experts`` up to ``zaya.DENSE``) holds no grouped product and
    reports 0 whatever the pool's platform, and names ``experts_dense``,
    the experts it read whole; a program that routes reports the visits on
    a TPU and no ``experts_dense``."""
    cfg = zaya.ZayaConfig.from_hf(SMALL)
    L, E = cfg.num_hidden_layers, cfg.num_experts
    assert (rows * E <= zaya.DENSE) == dense
    counts = np.full((L, E), rows // E - 1, np.int32)
    skips = np.full((L,), E, np.int32)
    moved = np.asarray([rows * 7, 2 * rows], np.int32)
    before = HUB.snapshot().get("gen_moe_expert_reads_total", 0)
    attrs = zaya.observe(counts, skips, moved, rows, cfg, platform="tpu",
                         rows=rows)
    counted = HUB.snapshot()["gen_moe_expert_reads_total"] - before
    assert attrs["assignments"] == rows * L
    assert attrs["zero_tokens"] == L * E
    assert attrs["expert_reads"] == counted
    if dense:
        assert attrs["expert_reads"] == reads
        assert attrs["experts_dense"] == L * E
    else:
        assert attrs["expert_reads"] >= attrs["experts_hit"] == L * E
        assert "experts_dense" not in attrs
    cpu = zaya.observe(counts, skips, moved, rows, cfg, platform="cpu",
                       rows=rows)
    assert cpu["expert_reads"] == 0


def test_scopes_name_the_hlo(small):
    _ckpt, params, cfg = small
    engine = GenEngine(params, cfg, block_tokens=2, **ENGINE)
    pool = engine.pool
    lease = pool.alloc(40)

    def step(n):
        rows = engine._decode_inputs([_Seq(None, lease, n, 1)])[1]
        return engine._jdecode.lower(engine.params, rows, engine._prev_ids,
                                     *pool.arrays).as_text(debug_info=True)

    narrow, wide = step(9), step(70)
    prompt = engine._jprefill.lower(
        engine.params, np.zeros((1, 30), np.int32),
        np.asarray(lease.blocks[:15] + [lease.slot], np.int32),
        *pool.arrays).as_text(debug_info=True)
    lease.free()
    engine.stop()
    for scope in ("attn.cca", "attn.cca.mix", "moe.route", "moe.experts",
                  "moe.skip"):
        assert scope in narrow and scope in wide and scope in prompt, scope
    # up to two tiles a row the rectangle, the filled tiles past it
    assert "attn.tiles" not in narrow and "attn.tiles" in wide
    assert "kv.tiles" in wide and "attn.tiles" not in prompt
    # the layers are one loop, and the step's other loop the tiles'
    assert prompt.count("stablehlo.while") == 1
    assert narrow.count("stablehlo.while") == 1
    assert wide.count("stablehlo.while") == 2


def test_served_over_http_like_the_others(small, tmp_path):
    """``/generate`` through ``serve.install`` and the restore server: the
    tokens the engine's own ``generate`` gives."""
    import urllib.request

    from demodel_tpu import serve
    from demodel_tpu.restore.server import RestoreRegistry, RestoreServer
    from demodel_tpu.store import Store

    _ckpt, params, cfg = small
    prompt = _prompts((20,))[0]
    engine = serve.boot(params, cfg, block_tokens=4, **ENGINE)
    srv = RestoreServer(RestoreRegistry(Store(tmp_path / "s")),
                        host="127.0.0.1").start()
    try:
        want = engine.generate(prompt, 5, timeout=240)
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/generate",
            data=json.dumps({"prompt": prompt,
                             "max_new_tokens": 5}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=240) as resp:
            got = json.loads(resp.read())["tokens"]
        kv = engine.describe()["kv"]
        assert (kv["page"], kv["layers"], kv["num_slots"]) \
            == ("latent", 3, 4)
    finally:
        srv.stop()
        engine.stop()
        serve.install(None)
    assert got == want


def test_a_pulled_snapshot_is_built_by_its_model_type(small, tmp_path):
    from demodel_tpu.models import auto
    from demodel_tpu.sink.hbm import Placement
    from demodel_tpu.store import Store

    ckpt, params, _cfg = small
    assert "zaya" in auto.families()
    store = Store(tmp_path / "s")
    store.put("cfg", json.dumps(SMALL).encode())
    report = {"files": [{"name": "config.json", "key": "cfg"}]}
    weights = {name: jnp.asarray(ckpt.tensor(name)) for name in ckpt.tensors}
    fn, built, cfg = auto.model_from_pull(
        store, report, placement=Placement(weights, None))
    assert fn is None and isinstance(cfg, zaya.ZayaConfig)
    assert jax.tree.structure(built) == jax.tree.structure(params)
    np.testing.assert_array_equal(built["experts_down"],
                                  params["experts_down"])


def test_family_counts_what_the_program_holds(small):
    """The program's tree is the checkpoint's tensors, one row of zeros
    more (layer 0's ``gamma``, which the checkpoint has not) and, for the
    two temperatures a layer, a factor a mixed column."""
    _ckpt, params, cfg = small
    held = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(params))
    assert held == FAMILY.parameters(SMALL) + cfg.router_hidden_size \
        + cfg.num_hidden_layers * (cfg.mixed - cfg.num_key_value_heads)
    made = zaya.init_params(jax.random.key(0), cfg)
    assert jax.tree.structure(made) == jax.tree.structure(params)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree.leaves(made), jax.tree.leaves(params)))
    # the seeded fills: what init_params makes of the constant vectors is
    # what the loader makes of the checkpoint's; the depthwise taps and
    # gamma are drawn from the seed there (the family's ``tensors``)
    named = zaya.unpack(params["layers"]["vectors"], cfg)
    plain = zaya.unpack(made["layers"]["vectors"], cfg)
    drawn = {"conv0_w.0", "conv0_w.1", "router_gamma", "mixing"}
    for name in named:
        if name not in drawn:
            np.testing.assert_array_equal(plain[name], named[name])
    assert named["attn_merge.output_scale"].shape == (3, 64)
    assert named["conv0_w.1"].shape == (3, 96)
    assert (np.asarray(named["conv0_w.0"]) != np.asarray(
        named["conv0_w.1"])).all()
    # no stream enters layer 0; every later layer scales the one before's
    gamma = np.asarray(named["router_gamma"])
    assert not gamma[0].any() and gamma[1:].all()
    # a factor a mixed column: ones under the query heads
    assert named["temp"].shape == (3, 96)
    assert (np.asarray(named["temp"])[:, :64] == 1).all()
    np.testing.assert_array_equal(zaya.pack(named, cfg),
                                  params["layers"]["vectors"])


@pytest.mark.parametrize("key,value", [
    ("sliding_window", 4096), ("num_experts_per_tok", 2),
    ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("attention_bias", True), ("lm_head_bias", True),
    ("hidden_act", "gelu"), ("tie_word_embeddings", False),
    ("zaya_use_mod", False), ("zaya_use_eda", False), ("cca", False),
    ("scale_residual_merge", False),
    ("layer_types", ["hybrid", "hybrid_sliding", "hybrid"]),
    ("num_key_value_heads", 3), ("cca_time0", 0)])
def test_what_is_not_implemented_is_refused_by_name(key, value):
    config = json.loads(json.dumps(SMALL))
    config[key] = value
    with pytest.raises(ValueError, match=f"config field.* {key}="):
        zaya.ZayaConfig.from_hf(config)


def test_the_published_configuration_is_read_as_published():
    doc = json.loads((BENCH / "configs" / "zaya1-8b-l16.json").read_text())
    doc.pop("benchmark")
    cfg = zaya.ZayaConfig.from_hf(doc)
    assert cfg == zaya.ZayaConfig(num_hidden_layers=16, dtype="bfloat16")
    assert (cfg.q_dim, cfg.kv_dim, cfg.mixed, cfg.page_dim, cfg.tail_dim,
            cfg.rotary) == (1024, 256, 1280, 512, 2688, 64)
    assert cfg.rope_theta == 5e6


def test_a_mesh_with_ep_holds_the_same_layer(small, monkeypatch):
    """Under a mesh with an ``ep`` axis the experts' one stack is split
    over it and the routed parts summed: the same logits."""
    from demodel_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(zaya, "DENSE", 0)

    ckpt, params, cfg = small
    mesh = make_mesh(4, ep=4, tp=1)
    split, _cfg = _params(ckpt, SMALL, mesh=mesh)
    assert split["experts_down"].sharding.spec == jax.sharding.PartitionSpec(
        "ep")
    tokens = jnp.asarray(_prompts((12,)), jnp.int32)
    params32, cfg32 = _float32(params, cfg)
    split32 = jax.tree.map(lambda a: a.astype(jnp.float32), split)
    want, *_ = zaya.step_prefill(params32, tokens, cfg32)
    got, _w, counts, _s, _m = jax.jit(
        lambda p, t: zaya.step_prefill(p, t, cfg32, mesh=mesh))(split32,
                                                                 tokens)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    assert counts.shape == (3, 4)


def test_a_step_is_few_slice_updates(small):
    """All layers' new positions land by one slice update a row, whatever
    the depth, and all rows' tails by one select of the slots' array
    (``kvcache.Whole``), beside the three by which the scan stacks what its
    layers hand out (page rows, tails, counts)."""
    _ckpt, params, cfg = small
    engine = GenEngine(params, cfg, block_tokens=4, **ENGINE)
    pool = engine.pool
    lease = pool.alloc(8)
    rows = engine._decode_inputs([_Seq(None, lease, n, 1)
                                  for n in (9, 5, 3)])[1]
    text = engine._jdecode.lower(engine.params, rows, engine._prev_ids,
                                 *pool.arrays).as_text()
    lease.free()
    engine.stop()
    assert rows.shape[0] == 4
    assert text.count("stablehlo.dynamic_update_slice") == 4 + 3
