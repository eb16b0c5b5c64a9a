"""EXAONE-MoE through the normal path at a small size, held to the float32
reference of the benchmark's family file (``benchmark/lib/families/
exaone_moe.py``, which imports nothing of the program): hidden 64, eight
layers ``LLLG LLLG`` with layer 0 dense, window 8 under sequences of 40, 16
experts of which 4 are held (``ep_size`` 4), 4 a token.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demodel_tpu.models import exaone_moe, experts
from demodel_tpu.serve import GenEngine
from demodel_tpu.serve.scheduler import _Seq
from demodel_tpu.utils.metrics import HUB, labeled

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))

from lib import checkpoint, families, reference  # noqa: E402

SMALL = {
    "model_type": "exaone_moe", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 8,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "num_experts": 4, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "ep_size": 4, "ep_rank": 1,
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "hidden_act": "silu", "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "sliding_window": 8,
    # longer than the depth, as a checkpoint cut in depth keeps them
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"] * 2,
    "sliding_windows": [8, 8, 8, 0] * 2 + [0],
    "mlp_layer_types": ["dense"] + ["sparse"] * 8,
    "num_nextn_predict_layers": 0, "torch_dtype": "bfloat16",
}
SEED = 2147483700


def _params(ckpt, model: dict, mesh=None):
    cfg = exaone_moe.ExaoneMoeConfig.from_hf(model)
    weights = {name: jnp.asarray(ckpt.tensor(name)) for name in ckpt.tensors}
    params = exaone_moe.load_params(weights, cfg, mesh=mesh)
    assert not weights, sorted(weights)     # the loader took every tensor
    return params, cfg


@pytest.fixture(scope="module")
def small():
    ckpt = checkpoint.Checkpoint(SMALL, SEED, n_shards=2)
    return (ckpt, *_params(ckpt, SMALL))


def _prompts(n: int, length: int) -> list[list[int]]:
    rng = np.random.default_rng([SEED, 7])
    return [[int(t) for t in rng.integers(0, SMALL["vocab_size"], length)]
            for _ in range(n)]


def _engine_logits(engine, prompts, steps: int):
    """Each prompt prefilled through the engine's program into a lease,
    then ``steps`` decode steps of the ragged batch through the pool,
    teacher-forced with the prefill's and each step's own first choice.
    Returns per prompt ``(sequence fed, logits [1 + steps, V])``."""
    pool = engine.pool
    seqs, rows = [], []
    for prompt in prompts:
        lease = pool.alloc(pool.blocks_for(len(prompt) + steps))
        _ids, (logits, *_stats) = engine._prefill(prompt, lease)
        first = np.asarray(logits, np.float32)
        seqs.append(_Seq(None, lease, len(prompt), int(first[0].argmax())))
        rows.append([first[0]])
    fed = [list(p) for p in prompts]
    for _ in range(steps):
        for f, s in zip(fed, seqs):
            f.append(s.last_tok)
        _width, sent = engine._decode_inputs(seqs)
        _ids, (logits, *_stats) = pool.apply(
            engine._jdecode, engine.params, jax.device_put(sent),
            engine._prev_ids)
        out = np.asarray(logits, np.float32)
        for s, row, lg in zip(seqs, rows, out):
            s.length += 1
            s.last_tok = int(lg.argmax())
            row.append(lg)
    for s in seqs:
        s.lease.free()
    return [(f, np.stack(r)) for f, r in zip(fed, rows)]


def _served(ckpt, params, cfg):
    """What the engine's two programs give for three prompts (the longest
    five windows long) and 16 steps of their ragged batch, beside the
    float32 reference's logits for the same sequences."""
    engine = GenEngine(params, cfg, max_batch=4, queue_limit=8,
                       max_new_tokens=24, kv_mb=1, block_tokens=4)
    prompts = [p[:n] for p, n in zip(_prompts(3, 40), (40, 17, 9))]
    try:
        got = _engine_logits(engine, prompts, steps=16)
    finally:
        engine.stop()
    assert engine.pool.describe()["in_use_blocks"] == 0
    seqs = [f for f, _lg in got]
    wanted = [range(len(p) - 1, len(f)) for p, (f, _lg) in
              zip(prompts, got)]
    ref = reference.logits(ckpt, seqs, wanted)
    return got, wanted, [np.asarray(r)[:len(w)]
                         for r, w in zip(ref, wanted)], (ckpt, seqs)


def test_float32_program_is_the_reference(small):
    """The same weights computed in float32 by the program: prefill over
    five windows, decode through the pool with a window layer reading 3 of
    up to 16 slots, share 1 of 4 of the experts. No rounding to hide
    behind: 1e-4 on logits of order 1 (float32 sums in another order)."""
    import dataclasses

    ckpt, params, cfg = small
    got, _wanted, ref, _ = _served(
        ckpt, jax.tree.map(lambda a: a.astype(jnp.float32), params),
        dataclasses.replace(cfg, dtype="float32"))
    for (_fed, lg), r in zip(got, ref):
        np.testing.assert_allclose(lg, r, rtol=0, atol=1e-4)


class TestAgainstTheReference:
    """The bfloat16 program, prefill then decode through the pool, against
    the family's float32 ``logits``. The tolerances and their reasons:

    - rounding alone: a bfloat16 program's logits lie within 0.12 of the
      float32 reference's in the median row (logits are of order 1,
      bfloat16 keeps 8 bits, sixteen sub-layers each add a rounded,
      normalised term; read 0.06 here);
    - a top-k choice that differs at a near-tie exchanges a whole expert,
      and where one of the two is held and the other absent the row moves
      by up to ~1.2, not by a rounding: the reference's own bfloat16 mode
      differs from its float32 in 6 % of (token, layer) choices at this
      size, half of them on a held expert. So rows may lie further out,
      but at most a quarter of them beyond 0.3, and none beyond 2 (a
      wrong row lies ~4 out);
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


def test_shares_add_up_to_the_uncut_layer():
    """The parts that ``ep_rank`` 0-3 compute of one sparse layer, with
    what every chip computes alike (the shared expert) counted once, are
    the layer with all 16 experts held."""
    whole = exaone_moe.ExaoneMoeConfig.tiny(num_experts=16, ep_size=1)
    params = exaone_moe.init_params(jax.random.key(3), whole)
    layer = params["layers"][1]
    x = jax.random.normal(jax.random.key(4), (40, whole.hidden_size))
    live = jnp.ones((40,), bool)
    full, tokens = exaone_moe._moe(layer, x, live, whole, None)
    shared = experts.swiglu(x, layer["shared_gate_proj"],
                                layer["shared_up_proj"],
                                layer["shared_down_proj"])
    total, landed = shared, 0
    for rank in range(4):
        share = exaone_moe.ExaoneMoeConfig.tiny(ep_rank=rank)
        held = slice(rank * 4, rank * 4 + 4)
        mine = dict(layer, experts_gate_up=layer["experts_gate_up"][held],
                    experts_down=layer["experts_down"][held])
        part, n = exaone_moe._moe(mine, x, live, share, None)
        np.testing.assert_array_equal(n, tokens[held])
        total = total + (part - shared)
        landed += int(n.sum())
    assert landed == 40 * whole.num_experts_per_tok      # no token dropped
    np.testing.assert_allclose(total, full, rtol=0, atol=2e-5)


def test_ep_mesh_holds_the_same_layer():
    """Under a mesh with an ``ep`` axis the held experts are split over it
    and the parts summed: the same output and the same counts."""
    from demodel_tpu.parallel.mesh import make_mesh

    cfg = exaone_moe.ExaoneMoeConfig.tiny(ep_rank=2)
    params = exaone_moe.init_params(jax.random.key(5), cfg)
    tokens = jnp.asarray([_prompts(1, 40)[0]]) % cfg.vocab_size
    alone = jax.jit(lambda p: exaone_moe.step_prefill(p, tokens, cfg))(params)
    mesh = make_mesh(4, ep=4, tp=1)
    placed = jax.device_put(params, exaone_moe.param_shardings(cfg, mesh))
    assert placed["layers"][1]["experts_down"].sharding.spec[0] == "ep"
    split = jax.jit(lambda p: exaone_moe.step_prefill(
        p, tokens, cfg, mesh=mesh))(placed)
    np.testing.assert_allclose(split[0], alone[0], rtol=0, atol=1e-4)
    np.testing.assert_array_equal(split[2], alone[2])


def test_a_key_beyond_the_window_moves_a_full_layer_only():
    cfg = exaone_moe.ExaoneMoeConfig.tiny()
    layer = exaone_moe.init_params(jax.random.key(6), cfg)["layers"][0]
    x = jax.random.normal(jax.random.key(7), (1, 40, cfg.hidden_size))
    moved = x.at[0, 5].add(1.0)         # 34 behind the last position
    positions = jnp.arange(40)[None]

    def last(inp, window):
        out, _kv = exaone_moe._attn(layer, inp, cfg, positions,
                                    window=window)
        return np.asarray(out[0, -1])

    assert (last(x, 8) == last(moved, 8)).all()
    assert np.abs(last(x, 0) - last(moved, 0)).max() > 1e-3
    # and inside the window it does move the window layer
    near = x.at[0, 35].add(1.0)
    assert np.abs(last(x, 8) - last(near, 8)).max() > 1e-3


def test_window_layers_read_their_slots_of_the_table(small):
    """Decode through a table wider than a window's slots gives the logits
    of the same sequences through a table that is not: the slots a window
    layer reads are the ones that hold its window."""
    _ckpt, params, cfg = small
    assert exaone_moe.window_slots(8, 4) == 3
    prompts = [p[:n] for p, n in zip(_prompts(2, 40), (40, 21))]
    out = []
    for block_tokens in (4, 32):        # 16 slots against 2 (all read)
        engine = GenEngine(params, cfg, max_batch=2, queue_limit=4,
                           max_new_tokens=8, kv_mb=1,
                           block_tokens=block_tokens)
        try:
            out.append(_engine_logits(engine, prompts, steps=6))
        finally:
            engine.stop()
    for (fed_a, a), (fed_b, b) in zip(*out):
        assert fed_a == fed_b
        np.testing.assert_allclose(a, b, rtol=0, atol=0.05)


def test_assignments_are_counted(small):
    _ckpt, params, cfg = small
    names = [labeled("gen_moe_assignments_total", held=h)
             for h in ("true", "false")]
    before = HUB.snapshot()
    engine = GenEngine(params, cfg, max_batch=2, queue_limit=4,
                       max_new_tokens=8, kv_mb=1, block_tokens=4).start()
    try:
        reqs = [engine.submit(p, n) for p, n in
                zip(_prompts(2, 12), (5, 3))]
        for r in reqs:
            r.result(timeout=240)
    finally:
        engine.stop()
    after = HUB.snapshot()
    # every prompt token and every token fed back chose K experts in every
    # sparse layer (a request's last token is never fed)
    tokens = 2 * 12 + (5 - 1) + (3 - 1)
    rose = [after[n] - before.get(n, 0) for n in names]
    assert sum(rose) == cfg.num_experts_per_tok * tokens * cfg.sparse_layers
    assert 0 < rose[0] < rose[1]        # a quarter of the experts is here
    # up to a slab of assignments a layer the grouped products run over
    # every one of them, landed or not
    assert after["gen_moe_rows_computed_total"] \
        - before.get("gen_moe_rows_computed_total", 0) == sum(rose) >= rose[0]
    assert after["gen_moe_experts_hit_total"] \
        > before.get("gen_moe_experts_hit_total", 0)


def test_family_counts_what_the_program_holds(small):
    _ckpt, params, _cfg = small
    held = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(params)
               if leaf.ndim >= 2)
    assert held == families.of(SMALL).parameters(SMALL)


def test_unknown_model_type_still_fails_by_name(tmp_path):
    from demodel_tpu.models import auto
    from demodel_tpu.sink.hbm import Placement
    from demodel_tpu.store import Store

    store = Store(tmp_path / "s")
    store.put("cfg", json.dumps({"model_type": "mamba9"}).encode())
    report = {"files": [{"name": "config.json", "key": "cfg"}]}
    with pytest.raises(ValueError, match="unsupported model_type 'mamba9'"):
        auto.model_from_pull(store, report, placement=Placement({}, None))
    with pytest.raises(ValueError, match="step_prefill and step_decode"):
        from demodel_tpu.models.gpt2 import GPT2Config

        GenEngine({"embed": jnp.zeros((2, 2))}, GPT2Config())
