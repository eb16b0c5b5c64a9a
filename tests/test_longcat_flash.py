"""LongCat-Flash through the normal path at a small size, held to the
float32 reference of the benchmark's family file (``benchmark/lib/families/
longcat_flash.py``, which imports nothing of the program, writes the
attention in its expanded form at every position, multiplies the two latent
scales where the equations put them and runs the expert layer as a plain
loop with the identity branch): hidden 64, two double layers (four latent
sublayers in the pool), 4 heads of 16 | 8 over a latent of 32 | 8, a router
of 16 routed and 8 identity experts, 6 a token, a quarter of the routed
ones held.
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

from demodel_tpu.models import latent
from demodel_tpu.models import longcat_flash as lf
from demodel_tpu.serve import GenEngine, kvcache
from demodel_tpu.serve.scheduler import _Seq
from demodel_tpu.utils.metrics import HUB, labeled
from tests.test_exaone_moe import _engine_logits

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))

from lib import checkpoint, families, reference  # noqa: E402

SMALL = {
    "model_type": "longcat_flash", "hidden_size": 64, "ffn_hidden_size": 128,
    "expert_ffn_hidden_size": 32, "num_layers": 2, "num_attention_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "vocab_size": 512,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "n_routed_experts": 4, "ep_size": 4, "ep_rank": 1,
    "zero_expert_num": 8, "zero_expert_type": "identity", "moe_topk": 6,
    "routed_scaling_factor": 6, "attention_method": "MLA",
    "attention_bias": False, "hidden_act": "silu", "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "max_position_embeddings": 131072,
    "torch_dtype": "bfloat16",
}
SEED = 2147483801
ENGINE = dict(max_batch=4, queue_limit=8, max_new_tokens=24, kv_mb=1)
FAMILY = families.of(SMALL)


def _params(ckpt, model: dict, mesh=None):
    cfg = lf.LongcatFlashConfig.from_hf(model)
    weights = {name: jnp.asarray(ckpt.tensor(name)) for name in ckpt.tensors}
    params = lf.load_params(weights, cfg, mesh=mesh)
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
    batch (absorbed, through the latent pages of both sublayers a layer,
    teacher-forced with each step's own first choice), beside the float32
    reference's logits for the same sequences."""
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
    two attentions a layer expanded, every decode step absorbed over the
    latent pages (which the prefill wrote, sublayer ``i`` of layer ``l`` at
    paging layer ``2 l + i``), the two scales folded into the latent norms,
    the expert layer's result added an attention and a dense block late,
    against the reference's expanded form at every position with the
    scales where the equations have them. No rounding to hide behind: 2e-4
    on logits of order 1 (float32 sums in another order; a latent scaled by
    ``12 ** 0.5`` before or after a product). Past two tiles under 4 heads
    and under 32."""
    ckpt, params, cfg = small
    if heads != cfg.num_attention_heads:
        model = dict(SMALL, num_attention_heads=heads)
        ckpt = checkpoint.Checkpoint(model, SEED, n_shards=2)
        params, cfg = _params(ckpt, model)
    got, _wanted, ref, _ = _served(ckpt, *_float32(params, cfg),
                                   lengths=lengths, block_tokens=block_tokens)
    for (_fed, lg), r in zip(got, ref):
        np.testing.assert_allclose(lg, r, rtol=0, atol=2e-4)


class TestAgainstTheReference:
    """The bfloat16 program, prefill then decode through the latent pages,
    against the family's float32 ``logits``. The tolerances and their
    reasons:

    - rounding alone: a bfloat16 program's logits lie within 0.03 of the
      float32 reference's in the median row (0.022 here; logits are of
      order 1, bfloat16 keeps 8 bits, ten sub-layers each add a rounded
      term, and the absorbed step rounds the latent, the folded query and
      the weighted latent once more than the expanded form does);
    - a top-k choice that differs at a near-tie exchanges a whole expert,
      and where one of the two is held or an identity and the other absent
      the row moves by tenths, not by a rounding (an identity expert adds
      the normed residual itself, times up to 6 p; the program's router
      reads the residual as bfloat16 holds it, the reference's modes read
      it unrounded, so the program meets more such ties than they do). So
      rows may lie further out, but at most a tenth of them beyond 0.3, and
      none beyond 2 (a wrong row lies ~4 out);
    - by the median row the program lies no further from the reference than
      twice what the reference's own ``bfloat16`` mode does (1.5 x here),
      and the int8 mode put in the program's place lies more than three
      times as far (3.7 x): a program computing in the precision below
      fails here. The chip's statistic (how far below the reference's best
      the first choices lie, on average) is read too, but on a hundred rows
      of this toy it is two or three near-ties' (the queries, keys and
      values are filled at unit variance, so most rows' first choice
      stands in every mode): the program's reads below the int8 mode's."""

    @pytest.fixture(scope="class")
    def served(self, small):
        # a hundred rows
        return _served(*small, lengths=(40, 17, 9, 30), steps=24)

    def test_logits_agree(self, served):
        got, _wanted, ref, _ = served
        apart = np.concatenate([np.abs(lg - r).max(axis=1)
                                for (_f, lg), r in zip(got, ref)])
        assert np.median(apart) < 0.03, np.median(apart)
        assert (apart > 0.3).mean() <= 0.1, apart
        assert apart.max() < 2.0, apart.max()

    def test_precision_below_fails_where_bfloat16_passes(self, served):
        got, wanted, ref, (ckpt, seqs) = served

        def mode(name) -> list:
            low = reference.logits(ckpt, seqs, wanted, mode=name)
            return [np.asarray(lo)[:len(w)] for lo, w in zip(low, wanted)]

        def apart(rows) -> float:       # the median row's widest logit
            return float(np.median(np.concatenate(
                [np.abs(a - r).max(axis=1) for a, r in zip(rows, ref)])))

        def gap_mean(rows) -> float:
            return float(np.concatenate([
                reference.gaps_below_best(jnp.asarray(r), a.argmax(1))
                for a, r in zip(rows, ref)]).mean())

        program = [lg for _fed, lg in got]
        sound, control = mode("bfloat16"), mode("int8")
        assert apart(program) <= 2 * apart(sound), (apart(program),
                                                    apart(sound))
        assert apart(control) > 3 * apart(sound), (apart(control),
                                                   apart(sound))
        assert gap_mean(program) < gap_mean(control)


# ----------------------------------------------------- the layer's wiring


def _reference_layer(ckpt, li: int = 0) -> tuple[dict, dict, dict]:
    d = FAMILY._dims(SMALL)
    w = jax.tree.map(jnp.asarray, FAMILY._load(ckpt, d, li))
    return w, d, FAMILY._static(SMALL, d, "float32")


def test_the_expert_layer_skips_an_attention_and_a_dense_block(small):
    """One layer in float32 against the reference's ``(a, m, b, c, y)``:
    the program's ``y`` is the reference's; with the expert layer silenced
    (a scaling factor of 0) it is ``y - m``, so ``m`` enters after the
    second dense block and nowhere before; and the second sublayer's
    latent, which is made from ``b``, is the same with and without it, as
    is the first's. Each sublayer's latent is the reference's scaled
    ``[c_kv | k_r]`` of its own input."""
    ckpt, params, cfg = small
    params, cfg = _float32(params, cfg)
    T = 24
    x = jax.random.normal(jax.random.key(21), (1, T, cfg.hidden_size))
    positions = jnp.arange(T)[None]
    live = jnp.ones((1, T), bool)
    w, d, kw = _reference_layer(ckpt)
    T_pad = -(-T // FAMILY.BLOCK) * FAMILY.BLOCK
    a, m, b, c, y = (np.asarray(p)[:T] for p in FAMILY.layer_parts(
        jnp.pad(x[0], ((0, T_pad - T), (0, 0))),
        w, jnp.asarray(FAMILY.frequencies(SMALL)), **kw))
    assert np.abs(m).max() > 0.05           # there is something to skip

    def program(cfg):
        got, news, _tokens, _zeros = lf._layer(
            params["layers"][0], x, cfg, positions, live, (None, None), None)
        return np.asarray(got[0]), [np.asarray(n[0, :, 0]) for n in news]

    got, news = program(cfg)
    np.testing.assert_allclose(got, y, rtol=0, atol=2e-5)
    quiet, news_quiet = program(dataclasses.replace(
        cfg, routed_scaling_factor=0.0))
    np.testing.assert_allclose(quiet, y - m, rtol=0, atol=2e-5)
    for one, other in zip(news, news_quiet):
        np.testing.assert_array_equal(one, other)
    # the latents: sublayer 0's from N_0(x), sublayer 1's from N_1(b)
    C = cfg.kv_lora_rank
    for sub, inp, new in zip(w["sub"], (x[0], jnp.asarray(b)), news):
        h = reference.rms_norm(inp, sub["in_norm"], cfg.rms_norm_eps)
        kv = reference.linear(h, sub["kv_a"], "float32")
        c_kv = d["s_kv"] * reference.rms_norm(kv[:, :C], sub["kv_a_norm"],
                                              cfg.rms_norm_eps)
        np.testing.assert_allclose(new[:, :C], c_kv, rtol=0, atol=2e-5)
        assert new.shape[-1] == 128
        assert not new[:, cfg.latent.latent_dim:].any()


def test_each_sublayer_pages_into_its_own_pool_layer(small):
    """A prefill through the engine: paging layer ``2 l + i`` of the pool
    holds what sublayer ``i`` of layer ``l`` made, four different pages for
    two layers."""
    _ckpt, params, cfg = small
    params, cfg = _float32(params, cfg)
    engine = GenEngine(params, cfg, block_tokens=4, **ENGINE)
    prompt = _prompts((12,))[0]
    lease = engine.pool.alloc(3)
    engine._prefill(prompt, lease)
    held = np.asarray(engine.pool.k)[:, lease.blocks, 0]   # [4, 3, 4, 128]
    lease.free()
    engine.stop()
    _logits, latents, *_stats = lf.step_prefill(
        params, jnp.asarray([prompt]), cfg)
    assert len(latents) == 4 == lf.cache_spec(cfg).layers
    for li, new in enumerate(latents):
        np.testing.assert_allclose(held[li].reshape(12, 128),
                                   np.asarray(new)[0, :, 0], rtol=1e-5,
                                   atol=1e-6)
    for li in range(3):
        assert np.abs(held[li] - held[li + 1]).max() > 0.1


def test_scales_are_folded_into_the_latent_norms(small):
    """The loader holds ``q_a_layernorm`` times ``(64 / 48) ** 0.5`` and
    ``kv_a_layernorm`` times ``2 ** 0.5`` in float32 (ones in the seeded
    checkpoint), and one attention with the folded weights is the
    reference's, which multiplies ``W_qb c_q`` and the normalised ``c_kv``
    where the equations do; without the two keys nothing is folded and
    the attention is another."""
    ckpt, params, cfg = small
    s_q, s_kv = (64 / 48) ** 0.5, (64 / 32) ** 0.5
    assert cfg.latent_scales == pytest.approx((s_q, s_kv))
    attn = params["layers"][1]["sub"][1]["attn"]
    assert attn["q_a_norm"].dtype == attn["kv_a_norm"].dtype == jnp.float32
    np.testing.assert_allclose(attn["q_a_norm"], s_q, rtol=1e-6)
    np.testing.assert_allclose(attn["kv_a_norm"], s_kv, rtol=1e-6)
    plain = lf.LongcatFlashConfig.from_hf(
        dict(SMALL, mla_scale_q_lora=False, mla_scale_kv_lora=False))
    assert plain.latent_scales == (1.0, 1.0)
    weights = {name: jnp.asarray(ckpt.tensor(name)) for name in ckpt.tensors}
    unscaled = lf.load_params(weights, plain)
    np.testing.assert_array_equal(
        unscaled["layers"][1]["sub"][1]["attn"]["kv_a_norm"], 1.0)
    # one attention, float32, against the reference's
    w, d, _kw = _reference_layer(ckpt, 1)
    T = FAMILY.BLOCK
    x = jax.random.normal(jax.random.key(22), (T, cfg.hidden_size))
    want = FAMILY._attention(x, w["sub"][1], d, cfg.rms_norm_eps,
                             jnp.asarray(FAMILY.frequencies(SMALL)),
                             "float32")
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), attn)
    got, _new = latent.expanded(f32, x[None], cfg.latent,
                                jnp.arange(T)[None])
    np.testing.assert_allclose(got[0], want, rtol=0, atol=5e-5)
    other, _new = latent.expanded(
        jax.tree.map(lambda a: a.astype(jnp.float32),
                     unscaled["layers"][1]["sub"][1]["attn"]),
        x[None], plain.latent, jnp.arange(T)[None])
    assert np.abs(np.asarray(other[0]) - np.asarray(want)).max() > 0.05


# ------------------------------------------------------- the expert layer


def test_the_router_is_the_plain_loop():
    """:func:`longcat_flash.route` against a loop over tokens: softmax over
    all 24 outputs, the 6 largest of ``p + bias`` (the bias in the choice
    only), ``6 p`` of the chosen and no renormalisation; ties (equal
    logits, by construction) go to the lower index, in the program and in
    the family's reference alike."""
    cfg = lf.LongcatFlashConfig.tiny()
    R, K = cfg.router_width, cfg.moe_topk
    rng = np.random.default_rng(5)
    logits = rng.normal(size=(200, R)).astype(np.float32)
    logits[:50] = np.round(logits[:50])             # ties, many of them
    logits[50:60] = 0.0                             # all tied
    bias = rng.normal(size=R).astype(np.float32) * 0.05
    bias[3] = 1.0                                   # always chosen
    chosen, weights = (np.asarray(a) for a in lf.route(
        jnp.asarray(logits), jnp.asarray(bias), cfg))
    theirs, w_theirs = (np.asarray(a) for a in FAMILY.route(
        jnp.asarray(logits), jnp.asarray(bias), K,
        cfg.routed_scaling_factor))
    p = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    for t in range(len(logits)):
        score = p[t] + bias
        want = sorted(range(R), key=lambda e: (-score[e], e))[:K]
        assert chosen[t].tolist() == want == theirs[t].tolist()
        np.testing.assert_allclose(weights[t], 6.0 * p[t][want], rtol=1e-6)
        assert 3 in want and weights[t][want.index(3)] < 6.0 * p[t].max() \
            + 1e-6
    np.testing.assert_allclose(weights, w_theirs, rtol=1e-6)
    assert (weights.sum(axis=1) < 6.0).all()        # not renormalised
    assert np.abs(weights.sum(axis=1) - 6.0).max() > 0.5


def test_the_expert_layer_alone_is_the_reference(small):
    """The expert layer's output compared by itself, not through logits
    (on a share its routed part is a small part of the residual): the
    program's ``_moe`` in float32 against the reference's plain loop, the
    counts of held and identity assignments against a count over the
    reference's choice; and two made-up tokens, one whose 6 choices are all
    identity experts (its output is itself times the sum of their weights)
    and one that chose none (its identity part is zero)."""
    ckpt, params, cfg = small
    params, cfg = _float32(params, cfg)
    layer = params["layers"][0]
    w, d, kw = _reference_layer(ckpt)
    x = jax.random.normal(jax.random.key(23), (64, cfg.hidden_size))
    live = jnp.ones((64,), bool).at[60:].set(False)
    got, tokens, zeros = lf._moe(layer, x, live, cfg, None)
    want = FAMILY.moe(x, w, d, kw["scaling"], "float32")
    assert np.abs(np.asarray(want)).max() > 0.1
    np.testing.assert_allclose(got[:60], want[:60], rtol=0, atol=2e-5)
    chosen, _w = FAMILY.route(
        reference.linear(x, w["router"], "float32"), 0.0, d["K"], 6.0)
    chosen = np.asarray(chosen)[:60]
    assert int(zeros) == (chosen >= 16).sum()
    np.testing.assert_array_equal(
        tokens, [(chosen == 4 + e).sum() for e in range(4)])
    # a router made up so that token 0 wants the identity experts and token
    # 1 the first routed ones
    D, R = cfg.hidden_size, cfg.router_width
    router = np.zeros((D, R), np.float32)
    router[0, 16:] = 1.0
    router[1, :6] = 1.0
    made = dict(layer, router=jnp.asarray(router))
    x = np.zeros((2, D), np.float32)
    x[0, 0], x[1, 1] = 5.0, 5.0
    x[:, 2:] = np.asarray(jax.random.normal(jax.random.key(24), (2, D - 2)))
    got, tokens, zeros = lf._moe(made, jnp.asarray(x), jnp.ones((2,), bool),
                                 cfg, None)
    p = np.asarray(jax.nn.softmax(jnp.asarray(x @ router), axis=-1))
    np.testing.assert_allclose(got[0], x[0] * 6.0 * np.sort(p[0])[-6:].sum(),
                               rtol=1e-5, atol=1e-6)
    assert int(zeros) == 6              # token 0's six, none of token 1's
    # token 1 chose routed experts 0-5, of which this share holds 4 and 5
    np.testing.assert_array_equal(tokens, [1, 1, 0, 0])
    ref = FAMILY.moe(jnp.asarray(x), dict(w, router=jnp.asarray(router.T)),
                     d, 6.0, "float32")
    np.testing.assert_allclose(got, ref, rtol=0, atol=2e-5)


def test_shares_add_up_to_the_uncut_layer():
    """The routed parts that ``ep_rank`` 0-3 compute of one expert layer
    and the identity part, which every chip computes for its own tokens,
    counted once, are the layer with all 16 routed experts held beside its
    8 identity ones; no assignment is dropped or counted twice."""
    whole = lf.LongcatFlashConfig.tiny(n_routed_experts=16, ep_size=1)
    layer = lf.init_params(jax.random.key(3), whole)["layers"][1]
    x = jax.random.normal(jax.random.key(4), (40, whole.hidden_size))
    live = jnp.ones((40,), bool)
    full, tokens, zeros = lf._moe(layer, x, live, whole, None)
    # the identity part alone: a layer that holds no routed expert's rows
    nobody = dataclasses.replace(whole, n_routed_experts=4, ep_size=4,
                                 routed_scaling_factor=6.0)
    idle = dict(layer, experts_gate_up=layer["experts_gate_up"][:4] * 0,
                experts_down=layer["experts_down"][:4] * 0)
    identity, _n, zeros_once = lf._moe(idle, x, live, nobody, None)
    assert int(zeros_once) == int(zeros) > 0
    total, landed = identity, 0
    for rank in range(4):
        share = lf.LongcatFlashConfig.tiny(ep_rank=rank)
        held = slice(rank * 4, rank * 4 + 4)
        mine = dict(layer, experts_gate_up=layer["experts_gate_up"][held],
                    experts_down=layer["experts_down"][held])
        part, n, z = lf._moe(mine, x, live, share, None)
        np.testing.assert_array_equal(n, tokens[held])
        assert int(z) == int(zeros)         # every share sees them all
        total = total + (part - identity)
        landed += int(n.sum())
    assert landed + int(zeros) == 40 * whole.moe_topk
    np.testing.assert_allclose(total, full, rtol=0, atol=3e-5)


def test_ep_mesh_holds_the_same_layer():
    from demodel_tpu.parallel.mesh import make_mesh

    cfg = lf.LongcatFlashConfig.tiny(ep_rank=2)
    params = lf.init_params(jax.random.key(5), cfg)
    tokens = jnp.asarray(_prompts((40,))) % cfg.vocab_size
    alone = jax.jit(lambda p: lf.step_prefill(p, tokens, cfg))(params)
    mesh = make_mesh(4, ep=4, tp=1)
    placed = jax.device_put(params, lf.param_shardings(cfg, mesh))
    assert placed["layers"][1]["experts_down"].sharding.spec[0] == "ep"
    split = jax.jit(lambda p: lf.step_prefill(
        p, tokens, cfg, mesh=mesh))(placed)
    np.testing.assert_allclose(split[0], alone[0], rtol=0, atol=1e-4)
    np.testing.assert_array_equal(split[2], alone[2])
    np.testing.assert_array_equal(split[3], alone[3])


# ------------------------------------------------------- the latent page


def test_the_pool_counts_sublayers(small):
    """The module states two paging layers a layer of the model, each read
    whole by its own attention; the pool's bytes and what ``describe``
    says follow from that count and not from the model's."""
    _ckpt, _params, cfg = small
    spec = lf.cache_spec(cfg)
    assert cfg.num_layers == 2
    assert spec == kvcache.CacheSpec(4, 1, 128, values=32)
    pool = kvcache.KVBlockPool(spec, block_tokens=4, budget_mb=1,
                               dtype="bfloat16")
    assert pool.block_bytes == 4 * 4 * 128 * 2      # four sublayers, once
    assert len(pool.arrays) == pool.pages == 1 and pool.v is None
    assert pool.k.shape == (4, pool.num_blocks + 1, 1, 4, 128)
    said = pool.describe()
    assert (said["page"], said["value_dim"], said["layers"]) \
        == ("latent", 32, 4)
    assert said["block_bytes"] == pool.block_bytes
    # the published shapes: 8 sublayers of 512 | 64 and 64 of zeros, 10 240
    # B a position held, 9 216 of them the latent's
    published = lf.LongcatFlashConfig(num_layers=4, dtype="bfloat16")
    assert (published.latent.latent_dim, published.latent.page_dim) \
        == (576, 640)
    spec = lf.cache_spec(published)
    assert spec == kvcache.CacheSpec(8, 1, 640, values=512)
    big = kvcache.KVBlockPool(spec, block_tokens=16, budget_mb=1,
                              dtype="bfloat16")
    assert big.block_bytes == 16 * 10240
    assert big.describe()["layers"] == 8
    assert latent.observe(1, spec, published.latent,
                          "bfloat16")["latent_bytes"] == 9216
    assert FAMILY.position_bytes(dict(SMALL, num_layers=4, kv_lora_rank=512,
                                      qk_rope_head_dim=64)) == 9216


def test_a_pad_row_writes_the_scratch_block_only(small):
    """One sequence in a bucket of four beside a bystander's lease: after a
    prefill and five steps only the sequence's own blocks and the scratch
    block have changed, in every one of the four paging layers."""
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
    for li in range(4):
        assert (after[li, lease.blocks[:3]]
                != before[li, lease.blocks[:3]]).any()
    lease.free()
    bystander.free()
    engine.stop()


# ------------------------------------------------------ served, and seen


def test_spans_and_counters_name_the_identity_assignments(small):
    """``zero_tokens`` and ``assignments`` beside the held experts' counts
    and ``latent_bytes`` on the step's and the prefill's device span,
    through the module's ``observe``; the counters: an assignment is held,
    absent or an identity's, and the three add up to all of them."""
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
    position = 4 * 40 * 2           # four sublayers of 32 | 8 in bfloat16
    dev, = [s["attrs"] for s in spans if s["name"] == "serve.prefill-device"]
    assert dev["latent_bytes"] == 20 * position
    assert dev["assignments"] == 20 * 6 * 2
    steps = [s["attrs"] for s in spans if s["name"] == "serve.decode-step"]
    assert len(steps) == 5
    for i, a in enumerate(steps):
        assert a["latent_bytes"] == (20 + i) * position
        assert a["assignments"] == 6 * 2
        assert 0 <= a["zero_tokens"] <= a["assignments"] - a["expert_tokens"]
        assert {"expert_tokens", "experts_hit", "expert_rows"} <= set(a)
        assert a["expert_rows"] == a["assignments"]
    seen = [dev, *steps]
    assert sum(a["zero_tokens"] for a in seen) > 0
    after = HUB.snapshot()

    def counted(name):
        return after[name] - before.get(name, 0)

    kinds = {held: counted(labeled("gen_moe_assignments_total", held=held))
             for held in ("true", "false", "zero")}
    assert kinds["zero"] == sum(a["zero_tokens"] for a in seen)
    assert kinds["true"] == sum(a["expert_tokens"] for a in seen)
    assert sum(kinds.values()) == sum(a["assignments"] for a in seen)
    assert counted("gen_latent_kv_bytes_total") \
        == sum(a["latent_bytes"] for a in seen)
    # the family's reader of the benchmark, on these spans
    share = FAMILY.zero_share(
        type("Obs", (), {"window_spans": lambda self, name: [
            {"attrs": a} for a in steps]})(), "serve.decode-step")
    assert share == pytest.approx(
        100.0 * sum(a["zero_tokens"] for a in steps) / (5 * 12))


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
                  "moe.experts", "moe.zero", "ffn.dense"):
        assert scope in narrow and scope in wide, scope
    for scope in ("attn.latent", "moe.route", "moe.experts", "moe.zero",
                  "ffn.dense"):
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
        kv = engine.describe()["kv"]
        assert (kv["page"], kv["layers"]) == ("latent", 4)
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
    assert fn is None and isinstance(cfg, lf.LongcatFlashConfig)
    assert jax.tree.structure(built) == jax.tree.structure(params)
    np.testing.assert_array_equal(
        built["layers"][1]["sub"][1]["attn"]["w_uv"],
        params["layers"][1]["sub"][1]["attn"]["w_uv"])
    store.put("cfg", json.dumps(dict(SMALL, model_type="longcat")).encode())
    with pytest.raises(ValueError, match="longcat_flash"):
        auto.model_from_pull(store, report, placement=Placement({}, None))


def test_family_counts_what_the_program_holds(small):
    _ckpt, params, cfg = small
    held = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(params))
    assert held == FAMILY.parameters(SMALL)
    made = lf.init_params(jax.random.key(0), cfg)
    assert jax.tree.structure(made) == jax.tree.structure(params)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree.leaves(made), jax.tree.leaves(params)))


@pytest.mark.parametrize("key,value", [
    ("zero_expert_type", "copy"), ("attention_method", "MHA"),
    ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("attention_bias", True), ("q_lora_rank", None),
    ("hidden_act", "gelu"), ("sliding_window", 128)])
def test_what_is_not_implemented_is_refused_by_name(key, value):
    config = json.loads(json.dumps(SMALL))
    config[key] = value
    with pytest.raises(ValueError, match=f"config field {key}="):
        lf.LongcatFlashConfig.from_hf(config)


def test_a_selection_bias_in_the_checkpoint_enters_the_choice_only(small):
    """The loader takes ``e_score_correction_bias`` where the checkpoint
    has it (the seeded one holds zeros) and makes zeros where it has not;
    a bias on one identity expert moves the choice towards it and leaves
    the chosen weights ``6 p``."""
    ckpt, params, cfg = small
    weights = {name: jnp.asarray(ckpt.tensor(name)) for name in ckpt.tensors}
    name = "model.layers.0.mlp.router.e_score_correction_bias"
    assert not np.asarray(weights[name]).any()
    bias = np.zeros(cfg.router_width, np.float32)
    bias[20] = 1.0
    weights[name] = jnp.asarray(bias)
    biased = lf.load_params(weights, cfg)
    assert biased["layers"][0]["router_bias"].dtype == jnp.float32
    np.testing.assert_array_equal(biased["layers"][0]["router_bias"], bias)
    weights = {n: jnp.asarray(ckpt.tensor(n)) for n in ckpt.tensors
               if not n.endswith("e_score_correction_bias")}
    without = lf.load_params(weights, cfg)
    assert not np.asarray(without["layers"][0]["router_bias"]).any()
    x = jax.random.normal(jax.random.key(25), (32, cfg.hidden_size),
                          jnp.bfloat16)
    live = jnp.ones((32,), bool)
    _y, _n, plain = lf._moe(params["layers"][0], x, live, cfg, None)
    _y, _n, drawn = lf._moe(biased["layers"][0], x, live, cfg, None)
    assert int(drawn) > int(plain)
