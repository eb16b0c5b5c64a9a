"""Qwen3-Next through the normal path at a small size, held to the float32
reference of the benchmark's family file (``benchmark/lib/families/
qwen3_next.py``, which imports nothing of the program): hidden 64, eight
layers ``G G G F`` twice, 2 key and 4 value heads of 16 in the Gated
DeltaNet layers, 4 query / 2 KV heads of 16 with a quarter rotated in the
attention layers, 16 experts of which 4 are held (``ep_size`` 4), 4 a token.
Prompts of 150, 70 and 9 positions: over two chunks of the scan and a part,
one and a part, less than one.
"""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from demodel_tpu.models import experts, qwen3_next
from demodel_tpu.serve import GenEngine, kvcache
from demodel_tpu.serve.scheduler import _Seq
from demodel_tpu.utils.metrics import HUB, labeled
from tests.test_exaone_moe import _engine_logits

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))

from lib import checkpoint, families, reference  # noqa: E402

SMALL = {
    "model_type": "qwen3_next", "hidden_size": 64, "intermediate_size": 160,
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "partial_rotary_factor": 0.25,
    "rope_theta": 10000000, "rope_scaling": None,
    "full_attention_interval": 4, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_experts": 4, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "ep_size": 4, "ep_rank": 1, "decoder_sparse_step": 1,
    "mlp_only_layers": [], "use_sliding_window": False,
    "hidden_act": "silu", "rms_norm_eps": 1e-6, "vocab_size": 512,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
}
SEED = 2147483900
LENGTHS = (150, 70, 9)


def _params(ckpt, model: dict, mesh=None):
    cfg = qwen3_next.Qwen3NextConfig.from_hf(model)
    weights = {name: jnp.asarray(ckpt.tensor(name)) for name in ckpt.tensors}
    params = qwen3_next.load_params(weights, cfg, mesh=mesh)
    assert not weights, sorted(weights)     # the loader took every tensor
    return params, cfg


class _Shaken:
    """A checkpoint whose vectors are not the trivial fills: the weights of
    the zero-centred norms, ``A_log``, ``dt_bias`` and the gated norm get
    seeded values of the size trained ones have, so that ``1 + w``, the
    decay and the plain weight are all exercised."""

    def __init__(self, ckpt):
        self._ckpt = ckpt
        self.config, self.tensors = ckpt.config, ckpt.tensors

    def tensor(self, name: str):
        t = self._ckpt.tensor(name)
        if t.ndim != 1:
            return t
        rng = np.random.default_rng([SEED, len(name), sum(name.encode())])
        return (t.astype(np.float32)
                + rng.uniform(-0.5, 0.5, t.shape)).astype(t.dtype)


@pytest.fixture(scope="module")
def small():
    ckpt = _Shaken(checkpoint.Checkpoint(SMALL, SEED, n_shards=2))
    return (ckpt, *_params(ckpt, SMALL))


def _prompts(lengths=LENGTHS) -> list[list[int]]:
    rng = np.random.default_rng([SEED, 7])
    return [[int(t) for t in rng.integers(0, SMALL["vocab_size"], n)]
            for n in lengths]


ENGINE = dict(max_batch=4, queue_limit=8, max_new_tokens=24, kv_mb=1,
              block_tokens=4)


def _served(ckpt, params, cfg, lengths=LENGTHS, **over):
    """What the engine's two programs give for three prompts and 12 steps
    of their ragged batch (three rows in a bucket of four: the fourth is a
    pad row), beside the float32 reference's logits for the same
    sequences."""
    engine = GenEngine(params, cfg, **{**ENGINE, **over})
    prompts = _prompts(lengths)
    try:
        got = _engine_logits(engine, prompts, steps=12)
    finally:
        engine.stop()
    kv = engine.pool.describe()
    assert kv["in_use_blocks"] == 0 and kv["in_use_slots"] == 0
    seqs = [f for f, _lg in got]
    wanted = [range(len(p) - 1, len(f)) for p, (f, _lg) in
              zip(prompts, got)]
    ref = reference.logits(ckpt, seqs, wanted)
    return got, wanted, [np.asarray(r)[:len(w)]
                         for r, w in zip(ref, wanted)], (ckpt, seqs)


def _float32(params, cfg):
    return (jax.tree.map(lambda a: a.astype(jnp.float32), params),
            dataclasses.replace(cfg, dtype="float32"))


# --------------------------------------------------- the recurrence itself


def _token_by_token(q, k, v, g, beta, state):
    """The recurrence as it is written down, a position at a time."""
    outs = []
    for t in range(q.shape[1]):
        o, state = qwen3_next.gated_delta_step(
            q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], state)
        outs.append(o)
    return jnp.stack(outs, axis=1), state


@pytest.mark.parametrize("T,chunk,carried", [
    (128, 64, False),       # whole chunks
    (150, 64, False),       # two chunks and a part
    (9, 64, False),         # less than one
    (70, 16, True),         # another chunk, from a state that is not zero
])
def test_chunked_scan_is_the_recurrence(T, chunk, carried):
    B, Hk, r, dk, dv = 2, 2, 2, 16, 16
    keys = jax.random.split(jax.random.key(T), 6)
    unit = qwen3_next._l2
    q = unit(jax.random.normal(keys[0], (B, T, Hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], (B, T, Hk, dk)))
    v = jax.random.normal(keys[2], (B, T, Hk, r, dv))
    g = -jax.nn.softplus(jax.random.normal(keys[3], (B, T, Hk, r)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (B, T, Hk, r)))
    state = jax.random.normal(keys[5], (B, Hk, r, dk, dv)) if carried \
        else jnp.zeros((B, Hk, r, dk, dv))
    want_o, want_s = _token_by_token(q, k, v, g, beta, state)
    got_o, got_s = jax.jit(qwen3_next.gated_delta_chunks,
                           static_argnames="chunk")(
        q, k, v, g, beta, state if carried else None, chunk=chunk)
    np.testing.assert_allclose(got_o, want_o, rtol=0, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, rtol=0, atol=2e-5)


# ------------------------------------------------- against the reference


@pytest.mark.parametrize("slab", [experts.SLAB, 128])
def test_float32_program_is_the_reference(small, slab, monkeypatch):
    """The same weights computed in float32 by the program: the chunked
    scan over 150, 70 and 9 positions, then decode through pages and
    slots with a pad row riding along, share 1 of 4 of the experts. No
    rounding to hide behind: 1e-4 on logits of order 1 (float32 sums in
    another order). With a slab of 128 assignments the prompts' 600 and
    280 a layer go through the experts' loop over the landed slabs, the
    9-token prompt and the steps not."""
    monkeypatch.setattr(experts, "SLAB", slab)
    ckpt, params, cfg = small
    got, _wanted, ref, _ = _served(ckpt, *_float32(params, cfg))
    for (_fed, lg), r in zip(got, ref):
        np.testing.assert_allclose(lg, r, rtol=0, atol=1e-4)


@pytest.mark.parametrize("lengths", [LENGTHS, (60, 30, 9)],
                         ids=["wide", "across"])
def test_a_wide_step_follows_the_tiles_its_rows_have_filled(small, lengths):
    """Blocks of 2 positions, so that a tile holds 32: rows of 150, 70 and
    9 positions and a pad row, 12 steps at a width of 256 slots (sixteen
    tiles a row, of which the rows have filled 5 to 6, 3 and 1, the pad
    row none). Each attention layer gathers a chunk of its rows' filled
    tiles from the pool where they lie: float32 logits and greedy ids are
    the reference's. The same with a longest row of 60 positions, which
    passes two tiles at its sixth step: five steps over the rectangle of
    32 slots, then seven over the tiles of 256."""
    ckpt, params, cfg = small
    got, _wanted, ref, _ = _served(ckpt, *_float32(params, cfg),
                                   lengths=lengths, block_tokens=2)
    for (_fed, lg), r in zip(got, ref):
        np.testing.assert_allclose(lg, r, rtol=0, atol=1e-4)
        assert (lg.argmax(axis=-1) == r.argmax(axis=-1)).all()
    engine = GenEngine(params, cfg, **{**ENGINE, "block_tokens": 2})
    lease = engine.pool.alloc(81)
    width, rows = engine._decode_inputs([_Seq(None, lease, 161, 1)])
    step = engine._jdecode.lower(engine.params, rows, engine._prev_ids,
                                 *engine.pool.arrays).as_text(debug_info=True)
    lease.free()
    engine.stop()
    assert width == 512 and "kv.tiles" in step and "attn.tiles" in step


class TestAgainstTheReference:
    """The bfloat16 program, prefill then decode through pages and slots,
    against the family's float32 ``logits``. The tolerances and their
    reasons are EXAONE-MoE's (``tests/test_exaone_moe.py``), whose expert
    layer this family shares:

    - rounding alone: a bfloat16 program's logits lie within 0.2 of the
      float32 reference's in the median row (logits of order 1, bfloat16
      keeps 8 bits, sixteen sub-layers each add a rounded term to a
      residual stream that no norm resets, and the norms' ``1 + w`` reach
      1.5 here; the recurrent state is carried in float32, so a long
      prompt adds nothing; read 0.122, the reference's own bfloat16 mode
      0.149, its int8 mode 0.416);
    - a top-k choice that differs at a near-tie exchanges a whole expert
      (the reference's own bfloat16 mode differs from its float32 in 115
      of 1 952 (token, layer) choices here, 52 of them on a held expert):
      such rows lie further out, but at most 45 % of them beyond 0.3 (read
      31 %, int8 67 %), and none beyond 4 (read 2.85; a wrong row lies ~4
      out);
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
        assert np.median(apart) < 0.2, np.median(apart)
        assert (apart > 0.3).mean() <= 0.45, apart
        assert apart.max() < 4.0, apart.max()

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
    """The parts that ``ep_rank`` 0-3 compute of one expert layer, with
    what every chip computes alike (the gated shared expert) counted once,
    are the layer with all 16 experts held."""
    whole = qwen3_next.Qwen3NextConfig.tiny(num_experts=16, ep_size=1)
    params = qwen3_next.init_params(jax.random.key(3), whole)
    layer = params["layers"][1]
    x = jax.random.normal(jax.random.key(4), (40, whole.hidden_size))
    live = jnp.ones((40,), bool)
    full, tokens = qwen3_next._moe(layer, x, live, whole, None)
    shared = experts.swiglu(x, layer["shared_gate_proj"],
                            layer["shared_up_proj"],
                            layer["shared_down_proj"]) \
        * jax.nn.sigmoid(x @ layer["shared_gate"])
    total, landed = shared, 0
    for rank in range(4):
        share = qwen3_next.Qwen3NextConfig.tiny(ep_rank=rank)
        held = slice(rank * 4, rank * 4 + 4)
        mine = dict(layer, experts_gate_up=layer["experts_gate_up"][held],
                    experts_down=layer["experts_down"][held])
        part, n = qwen3_next._moe(mine, x, live, share, None)
        np.testing.assert_array_equal(n, tokens[held])
        total = total + (part - shared)
        landed += int(n.sum())
    assert landed == 40 * whole.num_experts_per_tok      # no token dropped
    np.testing.assert_allclose(total, full, rtol=0, atol=2e-5)


def test_family_counts_what_the_program_holds(small):
    _ckpt, params, _cfg = small
    held = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(params)
               if leaf.ndim >= 2)
    assert held == families.of(SMALL).parameters(SMALL)


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
    ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
    ("use_sliding_window", True), ("hidden_act", "gelu")])
def test_what_is_not_implemented_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        qwen3_next.Qwen3NextConfig.from_hf({**SMALL, key: value})


# ------------------------------------------------------ pages and slots


def _state(pool):
    return {name: np.asarray(a) for name, a in pool.state.items()}


def test_the_module_states_its_cache(small):
    """Two of eight layers page; the six others keep a slot: float32 states
    and the convolution's three last inputs."""
    _ckpt, params, cfg = small
    spec = qwen3_next.cache_spec(cfg)
    assert spec[:3] == (2, 2, 16)
    assert spec.state == (("gdn_state", (6, 4, 16, 16), "float32"),
                          ("gdn_conv", (6, 3, 128), "bfloat16"))
    engine = GenEngine(params, cfg, **ENGINE)
    pool = engine.pool
    assert pool.k.shape[0] == 2 and pool.num_slots == 4
    assert pool.state["gdn_state"].shape == (6, 5, 4, 16, 16)
    assert pool.state["gdn_conv"].shape == (6, 5, 3, 128)
    assert pool.slot_bytes == 6 * (4 * 16 * 16 * 4 + 3 * 128 * 2)
    # the budget pays for the slots first, the blocks with the rest
    assert pool.num_blocks == ((1 << 20) - 4 * pool.slot_bytes) \
        // pool.block_bytes
    engine.stop()


def test_a_pad_row_writes_the_scratch_slot_and_block_only(small):
    """One sequence in a bucket of... two, next to a bystander's lease that
    rides no step: after a prefill and three steps the bystander's slot and
    blocks hold what they held, and so does every slot and block nobody
    leased; only the sequence's own and the scratch ones changed."""
    _ckpt, params, cfg = small
    engine = GenEngine(params, cfg, **ENGINE)
    pool = engine.pool
    bystander = pool.alloc(2)
    marked = tuple(jnp.full(a.shape, 3, a.dtype) for a in pool.arrays)
    pool.arrays = jax.jit(lambda *a: a, out_shardings=pool.shardings)(
        *marked)
    before_k, before = np.asarray(pool.k), _state(pool)
    prompt = _prompts((9,))[0]
    lease = pool.alloc(pool.blocks_for(len(prompt) + 3))
    _ids, (logits, *_s) = engine._prefill(prompt, lease)
    seqs = [_Seq(None, lease, len(prompt),
                 int(np.asarray(logits)[0].argmax()))]
    for _ in range(3):
        # a bucket of four with three pad rows: built by hand from the one
        # real row, as _decode_inputs pads it
        _w, rows = engine._decode_inputs(seqs)
        rows = np.concatenate([rows, np.repeat(rows[:1], 3, axis=0)])
        rows[1:, 1] = 0                          # length 0: a pad row
        rows[1:, 2] = pool.scratch_block
        rows[1:, 5] = pool.scratch_slot
        pool.apply(engine._jdecode, engine.params, jax.device_put(rows),
                   engine._prev_ids)
        seqs[0].length += 1
    after_k, after = np.asarray(pool.k), _state(pool)
    mine = set(lease.blocks) | {pool.scratch_block}
    for b in range(pool.num_blocks + 1):
        same = (after_k[:, b] == before_k[:, b]).all()
        assert same == (b not in mine) or b in lease.blocks[3:], b
    assert bystander.slot != lease.slot
    for name in after:
        for s in range(pool.num_slots + 1):
            same = (after[name][:, s] == before[name][:, s]).all()
            assert same == (s not in (lease.slot, pool.scratch_slot)), \
                (name, s)
    lease.free()
    bystander.free()
    engine.stop()


def test_a_slot_taken_again_carries_nothing_over(small):
    """The same requests on a fresh pool, and once other requests have used
    and returned every slot and most blocks of it: the same logits."""
    _ckpt, params, cfg = small
    prompts = _prompts((70, 9))
    engine = GenEngine(*_float32(params, cfg), **ENGINE)
    want = _engine_logits(engine, prompts, steps=6)
    # all four slots hold other sequences' state and tails after this
    _engine_logits(engine, _prompts((150, 70, 70, 9)), steps=5)
    assert engine.pool.in_use_slots == 0
    assert all(np.asarray(a).any() for a in engine.pool.arrays)
    got = _engine_logits(engine, prompts, steps=6)
    engine.stop()
    for (fed_a, a), (fed_b, b) in zip(want, got):
        assert fed_a == fed_b
        np.testing.assert_array_equal(a, b)


def _counters():
    snap = HUB.snapshot()
    return (snap.get("gen_state_slots_alloc_total", 0),
            snap.get("gen_state_slots_freed_total", 0))


@pytest.mark.parametrize("ending", ["stop", "cancel", "failed-step"])
def test_slots_and_blocks_all_come_back(small, ending, monkeypatch):
    """However a sequence ends — it finished, the engine was stopped under
    it, it was cancelled, the step it rode failed — its blocks and its slot
    are returned, and the slot counters balance."""
    _ckpt, params, cfg = small
    alloc0, freed0 = _counters()
    engine = GenEngine(params, cfg, **ENGINE).start()
    pool = engine.pool
    try:
        done = engine.submit(_prompts((9,))[0], 3)
        assert len(done.result(timeout=240)) == 3
        reqs = [engine.submit(p, 24) for p in _prompts((33, 20))]
        for r in reqs:      # both are running, a slot each
            next(r.iter_tokens(timeout=240))
        assert pool.in_use_slots == 2
        assert engine.describe()["kv"]["in_use_slots"] == 2
        if ending == "cancel":
            for r in reqs:
                r.cancel()
            for r in reqs:
                with pytest.raises(RuntimeError):
                    r.result(timeout=240)
        elif ending == "failed-step":
            real = engine._jdecode

            def broken(*args):
                engine._jdecode = real
                raise RuntimeError("injected")

            engine._jdecode = broken
            for r in reqs:
                with pytest.raises(RuntimeError, match="decode failed"):
                    r.result(timeout=240)
    finally:
        engine.stop()
    kv = pool.describe()
    assert kv["in_use_blocks"] == 0 and kv["in_use_slots"] == 0
    assert kv["budget"]["in_use_bytes"] == 0
    alloc1, freed1 = _counters()
    assert alloc1 - alloc0 == freed1 - freed0 == 3
    assert pool.in_use_slots == 0


def test_spans_name_the_state_and_the_experts(small):
    """``state_bytes`` on the step's and the prefill's device span (a row's
    slot read and written; a prompt's written), ``expert_tokens``,
    ``experts_hit`` and ``expert_rows`` through the module's ``observe``."""
    from demodel_tpu.utils import trace

    _ckpt, params, cfg = small
    held = labeled("gen_moe_assignments_total", held="true")
    before = HUB.snapshot()
    trace.reset()
    trace.enable()
    try:
        engine = GenEngine(params, cfg, **ENGINE).start()
        try:
            engine.generate(_prompts((20,))[0], 4, timeout=240)
        finally:
            engine.stop()
        spans = trace.buffer().snapshot()
    finally:
        trace.reset()
    slot = engine.pool.slot_bytes
    steps = [s["attrs"] for s in spans if s["name"] == "serve.decode-step"]
    assert steps and all(a["state_bytes"] == 2 * a["batch"] * slot
                         and a["experts_hit"] > 0 for a in steps)
    dev = [s["attrs"] for s in spans if s["name"] == "serve.prefill-device"]
    assert dev and dev[0]["state_bytes"] == slot and dev[0]["experts_hit"] > 0
    # under a slab of assignments a layer, the grouped products run over
    # all of them: K a token a layer
    each = cfg.num_experts_per_tok * cfg.num_hidden_layers
    assert dev[0]["expert_rows"] == 20 * each > dev[0]["expert_tokens"] > 0
    assert all(a["expert_rows"] == a["batch"] * each >= a["expert_tokens"]
               for a in steps)
    after = HUB.snapshot()
    rows = after["gen_moe_rows_computed_total"] \
        - before.get("gen_moe_rows_computed_total", 0)
    assert rows == dev[0]["expert_rows"] + sum(
        a["expert_rows"] for a in steps) >= after[held] - before.get(held, 0)


def test_served_over_http_like_the_others(small, tmp_path):
    """``/generate`` through ``serve.install`` and the restore server: the
    tokens the engine's own ``generate`` gives."""
    import json
    import urllib.request

    from demodel_tpu import serve
    from demodel_tpu.restore.server import RestoreRegistry, RestoreServer
    from demodel_tpu.store import Store

    _ckpt, params, cfg = small
    prompt = _prompts((20,))[0]
    engine = serve.boot(params, cfg, **ENGINE)
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
    finally:
        srv.stop()
        engine.stop()
        serve.install(None)
    assert got == want
    # PR 42 added ``values`` (a page of one array), 0 for every family
    # that pages K and V; the attention layers alone page
    assert kvcache.CacheSpec._fields == ("layers", "kv_heads", "head_dim",
                                         "state", "values")
    spec = qwen3_next.cache_spec(cfg)
    assert (spec.values, spec.layers) == (0, sum(cfg.full))
