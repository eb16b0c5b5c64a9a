"""A.X-K1 through the normal path at a small size, held to the float32
reference of the benchmark's family file (``benchmark/lib/families/
axk1.py``, which imports nothing of the program and writes the attention
in its expanded form at every position): hidden 64, a dense layer and three
sparse ones, 4 heads of 16 | 8 over a latent of 32 | 8, 16 experts in 4
groups of which 2 are kept, 4 a token, a quarter of them held.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demodel_tpu.models import axk1, experts, latent
from demodel_tpu.serve import GenEngine, kvcache
from demodel_tpu.serve.scheduler import _Seq
from demodel_tpu.utils.metrics import HUB
from tests.test_exaone_moe import _engine_logits

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))

from lib import checkpoint, families, reference  # noqa: E402

SMALL = {
    "model_type": "axk1", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "vocab_size": 512,
    "n_routed_experts": 4, "num_experts_per_tok": 4, "n_shared_experts": 1,
    "n_group": 4, "topk_group": 2, "ep_size": 4, "ep_rank": 1,
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "topk_method": "none", "seq_aux": True, "hidden_act": "silu",
    "attention_bias": False, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    # a trained context of 16 stretched by 8: the rows below lie past it,
    # and column pairs 0, 1-2 and 3 are plain, blended and interpolated
    "rope_scaling": {"type": "yarn", "factor": 8, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16},
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
}
SEED = 2147483701
ENGINE = dict(max_batch=4, queue_limit=8, max_new_tokens=24, kv_mb=1)


def _params(ckpt, model: dict, mesh=None):
    cfg = axk1.AxK1Config.from_hf(model)
    weights = {name: jnp.asarray(ckpt.tensor(name)) for name in ckpt.tensors}
    params = axk1.load_params(weights, cfg, mesh=mesh)
    assert not weights, sorted(weights)     # the loader took every tensor
    return params, cfg


@pytest.fixture(scope="module")
def small():
    ckpt = checkpoint.Checkpoint(SMALL, SEED, n_shards=2)
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
    prefilled, expanded, into a lease) and ``steps`` steps of their ragged
    batch (absorbed, through the latent page, teacher-forced with each
    step's own first choice), beside the float32 reference's logits for the
    same sequences."""
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


@pytest.mark.parametrize("lengths,block_tokens,heads", [
    ((40, 17, 9), 4, 4),    # a table of two tiles: the rectangle
    ((70, 33, 5), 2, 4),    # past 64 positions: the tiles the rows filled
    ((70, 33, 5), 2, 32),   # the same under 32 heads
], ids=["inside-two-tiles", "past-two-tiles", "past-two-tiles-32-heads"])
def test_float32_program_is_the_reference(small, lengths, block_tokens,
                                          heads):
    """The same weights computed in float32 by the program: the prompt's
    attention expanded, every decode step absorbed over the latent page
    (which the prefill wrote), against the reference's expanded form at
    every position. No rounding to hide behind: 1e-4 on logits of order 1
    (float32 sums in another order, and the absorbed form multiplies
    ``w_uk`` into the query before the scores, not into the key). Past two
    tiles under 4 heads and under 32."""
    ckpt, params, cfg = small
    if heads != cfg.num_attention_heads:
        model = dict(SMALL, num_attention_heads=heads,
                     num_key_value_heads=heads)
        ckpt = checkpoint.Checkpoint(model, SEED, n_shards=2)
        params, cfg = _params(ckpt, model)
    got, _wanted, ref, _ = _served(ckpt, *_float32(params, cfg),
                                   lengths=lengths, block_tokens=block_tokens)
    for (_fed, lg), r in zip(got, ref):
        np.testing.assert_allclose(lg, r, rtol=0, atol=1e-4)


class TestAgainstTheReference:
    """The bfloat16 program, prefill then decode through the latent page,
    against the family's float32 ``logits``. The tolerances and their
    reasons:

    - rounding alone: a bfloat16 program's logits lie within 0.12 of the
      float32 reference's in the median row (logits are of order 1,
      bfloat16 keeps 8 bits, eight sub-layers each add a rounded term, and
      the absorbed step rounds the latent, the folded query and the
      weighted latent once more than the expanded form does);
    - a top-k choice that differs at a near-tie exchanges a whole expert,
      and where one of the two is held and the other absent the row moves
      by tenths, not by a rounding. So rows may lie further out, but at
      most a quarter of them beyond 0.3, and none beyond 2 (a wrong row
      lies ~4 out);
    - under the reference, the program's first choices lie on average no
      further below the best than three times what the reference's own
      ``bfloat16`` mode reads, and the int8 mode put in the program's
      place reads more than that limit: a program computing in the
      precision below fails here."""

    @pytest.fixture(scope="class")
    def served(self, small):
        return _served(*small)

    def test_logits_agree(self, served):
        got, _wanted, ref, _ = served
        apart = np.concatenate([np.abs(lg - r).max(axis=1)
                                for (_f, lg), r in zip(got, ref)])
        assert np.median(apart) < 0.12, np.median(apart)
        assert (apart > 0.3).mean() <= 0.25, apart
        assert apart.max() < 2.0, apart.max()

    def test_precision_below_fails_where_bfloat16_passes(self, served):
        got, wanted, ref, (ckpt, seqs) = served

        def gap_mean(rows_of) -> float:
            return float(np.concatenate([
                reference.gaps_below_best(jnp.asarray(r), rows_of(i))
                for i, r in enumerate(ref)]).mean())

        def first_choices(mode):
            low = reference.logits(ckpt, seqs, wanted, mode=mode)
            return lambda i: np.asarray(low[i])[:len(wanted[i])].argmax(1)

        sound = gap_mean(first_choices("bfloat16"))
        program = gap_mean(lambda i: got[i][1].argmax(1))
        control = gap_mean(first_choices("int8"))
        limit = 3 * sound
        assert program <= limit, (program, sound)
        assert control > limit, (control, sound)


# ------------------------------------------------- the two attention paths


@pytest.mark.parametrize("wide,heads", [(False, 4), (True, 4), (True, 32)],
                         ids=["rectangle", "tiles", "tiles-32-heads"])
def test_absorbed_attention_is_the_expanded_one(wide, heads):
    """One layer's attention at the last position of each row, computed
    twice from the same weights: expanded over the row's whole prefix, and
    absorbed over a latent page that holds the prefix (one array, its
    values the first 32 columns of its keys), in a table of two tiles and
    in a wider one read by its filled tiles, under 4 heads and under 32.
    float32: 2e-5, the two orders of the same sums."""
    cfg = axk1.AxK1Config.tiny(num_attention_heads=heads)
    layer = axk1.init_params(jax.random.key(11), cfg)["layers"][0]
    bs = 2
    lengths = np.asarray([70, 33, 1, 64] if wide else [40, 17, 1, 64])
    if not wide:
        bs = 4
    T = int(lengths.max()) + 1
    x = jax.random.normal(jax.random.key(12), (len(lengths), T,
                                               cfg.hidden_size))
    positions = jnp.broadcast_to(jnp.arange(T), x.shape[:2])
    whole, new = latent.expanded(layer, x, cfg.latent, positions)
    # the page: row b's positions in its own blocks, dealt backwards
    slots = kvcache.table_slots(-(-T // bs))
    nb = len(lengths) * slots
    table = np.arange(nb)[::-1].reshape(len(lengths), slots)
    pad = slots * bs - T
    assert new.shape[-1] == cfg.page_dim == 128         # 32 | 8 | zeros
    assert not np.asarray(new[..., cfg.latent_dim:]).any()
    paged = np.zeros((1, nb + 1, 1, bs, cfg.page_dim), np.float32)
    rows = np.pad(np.asarray(new), ((0, 0), (0, pad), (0, 0), (0, 0)))
    paged[0, table] = rows.reshape(len(lengths), slots, bs, 1, -1) \
        .transpose(0, 1, 3, 2, 4)
    cache = kvcache.Paged(jnp.asarray(paged), None, jnp.asarray(table))
    assert cache.wide == wide
    at = jnp.asarray(lengths)
    step = jnp.take_along_axis(x, at[:, None, None], axis=1)
    past = cache.past(0, cache.filled(at))
    assert past[1] is None if not wide else past.v is None
    got, last = latent.absorbed(layer, step, cfg.latent, at[:, None], past)
    want = np.take_along_axis(np.asarray(whole),
                              lengths[:, None, None], axis=1)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    np.testing.assert_allclose(
        last, np.take_along_axis(np.asarray(new),
                                 lengths[:, None, None, None], axis=1),
        rtol=0, atol=1e-6)


def test_yarn_blends_between_the_correction_dimensions():
    """The published rotary (correction dimensions 10 and 23): of 32 column
    pairs the first 11 turn as plain rotary does, the last 9 a 32nd as
    fast, the 12 between are blended;
    cos and sin carry 1 and the scores ``192 ** -0.5 * (0.1 ln 32 + 1) **
    2``. The family's reference, written on its own, gives the same."""
    cfg = axk1.AxK1Config()
    inv, factor = axk1.yarn_frequencies(cfg)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 32, rtol=1e-6)
    between = inv[11:23] / plain[11:23]
    assert (np.diff(between) < 0).all() and between[0] < 1 \
        and between[-1] > 1 / 32
    assert factor == 1.0
    assert cfg.softmax_scale == pytest.approx(0.1309, abs=5e-5)
    published = dict(SMALL, qk_nope_head_dim=128, qk_rope_head_dim=64,
                     rope_scaling=dict(SMALL["rope_scaling"], factor=32,
                                       original_max_position_embeddings=4096))
    theirs, f2, scale = families.of(SMALL).yarn(published)
    np.testing.assert_allclose(theirs, inv, rtol=1e-6)
    assert f2 == 1.0 and scale == pytest.approx(cfg.softmax_scale)


# ------------------------------------------------------- the expert layer


@pytest.mark.parametrize("groups,kept,K", [(4, 2, 4), (8, 4, 8), (2, 1, 3),
                                           (1, 1, 4)])
def test_group_limited_choice_is_the_plain_loop(groups, kept, K):
    """:func:`axk1.choose` against a loop over tokens and groups."""
    R = 48
    cfg = axk1.AxK1Config.tiny(n_routed_experts=R, ep_size=1, n_group=groups,
                               topk_group=kept, num_experts_per_tok=K)
    s = np.asarray(jax.nn.sigmoid(jax.random.normal(jax.random.key(groups),
                                                    (200, R))))
    got = np.asarray(axk1.choose(jnp.asarray(s), cfg))
    size = R // groups
    for row, mine in zip(s, got):
        best = [max(row[g * size:(g + 1) * size]) for g in range(groups)]
        keep = sorted(range(groups), key=lambda g: -best[g])[:kept]
        allowed = [e for g in keep for e in range(g * size, (g + 1) * size)]
        want = sorted(allowed, key=lambda e: -row[e])[:K]
        assert mine.tolist() == want
        assert all(e // size in keep for e in mine)


@pytest.mark.parametrize("groups,kept", [(4, 2), (2, 1)],
                         ids=["a-group-a-share", "half-a-group-a-share"])
def test_shares_add_up_to_the_uncut_layer(groups, kept):
    """The parts that ``ep_rank`` 0-3 compute of one sparse layer, with
    what every chip computes alike (the shared expert) counted once, are
    the layer with all 16 experts held, whether a share holds a whole
    group of the router or half of one (as the benchmark's share does)."""
    over = dict(n_group=groups, topk_group=kept)
    whole = axk1.AxK1Config.tiny(n_routed_experts=16, ep_size=1, **over)
    layer = axk1.init_params(jax.random.key(3), whole)["layers"][1]
    x = jax.random.normal(jax.random.key(4), (40, whole.hidden_size))
    live = jnp.ones((40,), bool)
    full, tokens = axk1._moe(layer, x, live, whole, None)
    shared = experts.swiglu(x, layer["shared_gate_proj"],
                            layer["shared_up_proj"],
                            layer["shared_down_proj"])
    total, landed = shared, 0
    for rank in range(4):
        share = axk1.AxK1Config.tiny(ep_rank=rank, **over)
        held = slice(rank * 4, rank * 4 + 4)
        mine = dict(layer, experts_gate_up=layer["experts_gate_up"][held],
                    experts_down=layer["experts_down"][held])
        part, n = axk1._moe(mine, x, live, share, None)
        np.testing.assert_array_equal(n, tokens[held])
        total = total + (part - shared)
        landed += int(n.sum())
    assert landed == 40 * whole.num_experts_per_tok      # no token dropped
    np.testing.assert_allclose(total, full, rtol=0, atol=2e-5)


def test_ep_mesh_holds_the_same_layer():
    from demodel_tpu.parallel.mesh import make_mesh

    cfg = axk1.AxK1Config.tiny(ep_rank=2)
    params = axk1.init_params(jax.random.key(5), cfg)
    tokens = jnp.asarray(_prompts((40,))) % cfg.vocab_size
    alone = jax.jit(lambda p: axk1.step_prefill(p, tokens, cfg))(params)
    mesh = make_mesh(4, ep=4, tp=1)
    placed = jax.device_put(params, axk1.param_shardings(cfg, mesh))
    assert placed["layers"][1]["experts_down"].sharding.spec[0] == "ep"
    split = jax.jit(lambda p: axk1.step_prefill(
        p, tokens, cfg, mesh=mesh))(placed)
    np.testing.assert_allclose(split[0], alone[0], rtol=0, atol=1e-4)
    np.testing.assert_array_equal(split[2], alone[2])


# ------------------------------------------------------- the latent page


def test_the_pool_holds_a_page_of_one_array(small):
    """The module states one vector of 32 | 8 a position a layer, in a page
    as wide as the lanes; the pool makes one array of it (and a scratch
    block), counts a block once, and leases, frees and describes it as it
    does a pair. A family that pages K and V still gets its two arrays."""
    _ckpt, _params, cfg = small
    spec = axk1.cache_spec(cfg)
    assert spec == kvcache.CacheSpec(4, 1, 128, values=32)
    pool = kvcache.KVBlockPool(spec, block_tokens=4, budget_mb=1,
                               dtype="bfloat16")
    assert pool.block_bytes == 4 * 4 * 128 * 2      # once, not K and V
    assert pool.num_blocks == (1 << 20) // pool.block_bytes
    assert len(pool.arrays) == pool.pages == 1 and pool.v is None
    assert pool.k.shape == (4, pool.num_blocks + 1, 1, 4, 128)
    assert pool.scratch_block == pool.num_blocks and pool.state == {}
    said = pool.describe()
    assert (said["page"], said["value_dim"]) == ("latent", 32)
    lease = pool.alloc(5)
    assert pool.describe()["in_use_blocks"] == 5
    assert pool.budget.describe()["in_use_bytes"] == 5 * pool.block_bytes
    with pytest.raises(kvcache.PoolExhausted):
        pool.alloc(pool.num_blocks)
    lease.free()
    lease.free()
    assert pool.describe()["in_use_blocks"] == 0
    assert pool.budget.describe()["in_use_bytes"] == 0
    # the published shapes: 512 | 64 and 64 of zeros, 1 280 B a position a
    # layer (1 152 of them the latent's), 8 960 B over seven
    published = axk1.AxK1Config(num_hidden_layers=7, dtype="bfloat16")
    assert (published.latent_dim, published.page_dim) == (576, 640)
    big = kvcache.KVBlockPool(axk1.cache_spec(published), block_tokens=16,
                              budget_mb=1, dtype="bfloat16")
    assert big.block_bytes == 16 * 8960
    # the pair, as it was
    pair = kvcache.KVBlockPool(kvcache.CacheSpec(4, 2, 16), block_tokens=4,
                               budget_mb=1, dtype="bfloat16")
    assert len(pair.arrays) == pair.pages == 2
    assert pair.block_bytes == 2 * 4 * 4 * 2 * 16 * 2
    assert pair.k.shape == pair.v.shape == (4, pair.num_blocks + 1, 2, 4, 16)
    said = pair.describe()
    assert (said["page"], said["value_dim"]) == ("kv", 16)


def test_one_write_a_step_and_one_a_prefill():
    """``put_blocks`` and ``put_positions`` on a page of one array: the
    prompt's vectors land in the lease's blocks, the tail of the last one
    zero; a step's land at their rows' places and a pad row's in the
    scratch block; nothing else moves."""
    pool = kvcache.KVBlockPool(kvcache.CacheSpec(2, 1, 6, values=4),
                               block_tokens=4, budget_mb=1)
    marked = jax.jit(lambda a: a + 3.0, out_shardings=pool.sharding)(pool.k)
    blocks = jnp.asarray([7, 2], jnp.int32)
    new = [jnp.full((1, 5, 1, 6), 10.0 + li) for li in range(2)]
    k, = jax.jit(kvcache.put_blocks, static_argnums=1)(marked, None, new,
                                                       blocks)
    got = np.asarray(k)
    for li in range(2):
        assert (got[li, 7] == 10 + li).all()
        assert (got[li, 2, :, :1] == 10 + li).all()
        assert (got[li, 2, :, 1:] == 0).all()
    untouched = np.ones(got.shape[1], bool)
    untouched[[7, 2]] = False
    assert (got[:, untouched] == 3).all()
    step = [jnp.stack([jnp.full((1, 1, 6), 20.0 + li),
                       jnp.full((1, 1, 6), 30.0 + li)]) for li in range(2)]
    k, = jax.jit(kvcache.put_positions, static_argnums=1)(
        k, None, step, jnp.asarray([2, pool.scratch_block], jnp.int32),
        jnp.asarray([1, 0], jnp.int32))
    after = np.array(k)
    for li in range(2):
        assert (after[li, 2, 0, 1] == 20 + li).all()
        assert (after[li, pool.scratch_block, 0, 0] == 30 + li).all()
    after[:, 2, 0, 1] = got[:, 2, 0, 1]
    after[:, pool.scratch_block, 0, 0] = got[:, pool.scratch_block, 0, 0]
    np.testing.assert_array_equal(after, got)


def test_a_pad_row_writes_the_scratch_block_only(small):
    """One sequence in a bucket of four beside a bystander's lease: after a
    prefill and five steps only the sequence's own blocks and the scratch
    block have changed."""
    _ckpt, params, cfg = small
    engine = GenEngine(params, cfg, block_tokens=4, **ENGINE)
    pool = engine.pool
    bystander = pool.alloc(2)
    pool.arrays = jax.jit(lambda a: (a + 3,), out_shardings=pool.shardings)(
        pool.k)
    before = np.asarray(pool.k, np.float32)
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
    after = np.asarray(pool.k, np.float32)
    mine = np.zeros(after.shape[1], bool)
    mine[lease.blocks + [pool.scratch_block]] = True
    np.testing.assert_array_equal(after[:, ~mine], before[:, ~mine])
    assert (after[:, lease.blocks[:3]] != before[:, lease.blocks[:3]]).any()
    lease.free()
    bystander.free()
    engine.stop()


# ------------------------------------------------------ served, and seen


def test_spans_and_counters_name_the_latent_bytes(small):
    """``latent_bytes`` beside the experts' counts on the step's and the
    prefill's device span, from the lengths through the module's
    ``observe``; the counter."""
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
    position = 4 * 40 * 2               # four layers of 32 | 8 in bfloat16
    dev, = [s["attrs"] for s in spans if s["name"] == "serve.prefill-device"]
    assert dev["latent_bytes"] == 20 * position
    steps = [s["attrs"] for s in spans if s["name"] == "serve.decode-step"]
    assert len(steps) == 5
    for i, a in enumerate(steps):
        assert a["latent_bytes"] == (20 + i) * position
        assert {"expert_tokens", "experts_hit", "expert_rows"} <= set(a)
    after = HUB.snapshot()
    assert after["gen_latent_kv_bytes_total"] \
        - before.get("gen_latent_kv_bytes_total", 0) \
        == dev["latent_bytes"] + sum(a["latent_bytes"] for a in steps)


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
        np.asarray(lease.blocks[:15], np.int32),
        *pool.arrays).as_text(debug_info=True)
    lease.free()
    engine.stop()
    for scope in ("attn.latent", "attn.latent.absorb", "moe.route",
                  "moe.experts"):
        assert scope in narrow and scope in wide, scope
    for scope in ("attn.latent", "moe.route", "moe.experts"):
        assert scope in prompt, scope
    assert "attn.latent.absorb" not in prompt
    # up to two tiles a row the rectangle, the filled tiles past it
    assert "attn.tiles" not in narrow and "attn.tiles" in wide


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
        assert engine.describe()["kv"]["page"] == "latent"
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
    store = Store(tmp_path / "s")
    store.put("cfg", json.dumps(SMALL).encode())
    report = {"files": [{"name": "config.json", "key": "cfg"}]}
    weights = {name: jnp.asarray(ckpt.tensor(name)) for name in ckpt.tensors}
    fn, built, cfg = auto.model_from_pull(
        store, report, placement=Placement(weights, None))
    assert fn is None and isinstance(cfg, axk1.AxK1Config)
    assert jax.tree.structure(built) == jax.tree.structure(params)
    np.testing.assert_array_equal(built["layers"][2]["w_uv"],
                                  params["layers"][2]["w_uv"])


def test_family_counts_what_the_program_holds(small):
    _ckpt, params, _cfg = small
    held = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(params))
    assert held == families.of(SMALL).parameters(SMALL)


@pytest.mark.parametrize("key,value", [
    ("n_shared_experts", 2), ("moe_layer_freq", 2),
    ("rope_scaling.type", "linear"), ("rope_scaling", None),
    ("scoring_func", "softmax"), ("topk_method", "noaux_tc"),
    ("attention_bias", True), ("q_lora_rank", None), ("n_group", 5)])
def test_what_is_not_implemented_is_refused_by_name(key, value):
    config = json.loads(json.dumps(SMALL))
    group, _, leaf = key.rpartition(".")
    (config[group] if group else config)[leaf] = value
    with pytest.raises(ValueError, match=f"config field {key}="):
        axk1.AxK1Config.from_hf(config)


def test_a_selection_bias_in_the_checkpoint_is_refused(small):
    ckpt, _params, cfg = small
    weights = {name: jnp.asarray(ckpt.tensor(name)) for name in ckpt.tensors}
    weights["model.layers.1.mlp.gate.e_score_correction_bias"] = jnp.zeros(16)
    with pytest.raises(ValueError, match="e_score_correction_bias"):
        axk1.load_params(weights, cfg)
