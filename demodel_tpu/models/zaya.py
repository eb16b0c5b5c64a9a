"""ZAYA1 (``model_type`` ``zaya``): compressed convolutional attention
whose cache is a page of one array beside a convolution's tails, a router
that is an MLP with a stream of its own through the depth, one expert a
token of a set that is held whole, and a skip beside the experts.

A layer is an attention sublayer and an expert sublayer, each merged into
the residual by learned scales and biases. Two streams enter layer ``l``:
the residual ``x_t`` and the router's ``rho_t`` of the layer before (zero
into layer 0). With ``H`` query heads over ``Hkv`` cached heads of ``hd``,
``g = H / Hkv``, ``k0 = cca_time0``, ``k1 = cca_time1``; anything of the
sequence before position 0 is zero:

- ``h_t = RMSNorm(x_t)``; ``q~_t = W_q h_t`` (``H`` heads), ``k~_t = W_k
  h_t`` (``Hkv`` heads); **the values' second half is a token late**:
  ``v_t = [W_v1 h_t ; W_v2 h_{t-1}]``, each half ``Hkv hd / 2`` columns.
- **Mixing**, ``u_t = [q~_t ; k~_t]``: ``c0_t = b0 + sum_j w0[j] * u_{t -
  (k0 - 1) + j}`` (depthwise, causal), ``c1_t = b1 + sum_j W1[j] c0_{t -
  (k1 - 1) + j}`` with each ``W1[j]`` block-diagonal, one ``hd x hd`` block
  a head, query and key heads alike; no activation between the two; the
  sequence is padded once, on the left, by ``(k0 - 1) + (k1 - 1)`` zeros of
  ``u`` (so ``c0`` before position 0 is ``b0``).
- **The mean**: ``m_q^(i) = (q~^(i) + k~^(i // g)) / 2``, ``m_k^(j)`` the
  mean of ``m_q^(i)`` over the query heads of KV head ``j``; ``q^(i) =
  c1[q]^(i) + m_q^(i)``, ``k^(j) = c1[k]^(j) + m_k^(j)``.
- In float32 a head: ``q^ = q sqrt(hd) / |q|``, ``k^ = tau_j k sqrt(hd) /
  |k|`` with one learned ``tau_j`` a KV head; rotary on the first
  ``partial_rotary_factor hd`` columns of a head (rotate-half within them).
- Causal softmax attention of the ``H`` heads over the ``Hkv`` cached ones,
  scale ``hd ** -0.5`` (:func:`common.attend`); ``a_t = W_o out_t``.
- Merge: ``x'_t = s_r * (x_t + b_r) + s_o * (a_t + b_o)``.
- ``g_t = RMSNorm(x'_t)``; **the router's stream** ``rho^l_t = W_down g_t +
  gamma_l * rho^{l-1}_t`` goes on to layer ``l + 1``; ``s = W_3 gelu(W_2
  gelu(W_1 RMSNorm(rho^l_t) + c_1) + c_2) + c_3``, ``E + 1`` outputs; ``p =
  softmax(s)`` in float32, ``e* = argmax(p + beta)``.
- ``y_t = p[e*] Expert_{e*}(g_t)`` (a SwiGLU) for ``e* < E``; **output ``E``
  is the skip**, and a token routed there gets nothing from the sublayer.
  Merge as above with its own four vectors. After the last layer a norm,
  and the head is the embedding.

**The cache** (:func:`cache_spec`). Every layer pages and every layer keeps
a tail. The page is ONE array, a position's ``[v | k^]`` (``2 Hkv hd``
columns, 512 at the published widths, of which the first ``Hkv hd`` are the
values): a step's queries are ``H`` rows as wide as the page, query head
``i`` zero outside the columns of ``k^^(i // g)``, so that its scores are
its own key head's exactly, and of the value columns that come out under it
it keeps ``v^(i // g)``'s. The pool, ``attend`` and (in a program lowered
for a TPU) the kernel that reads the filled tiles where they lie
(:mod:`demodel_tpu.ops.latent_tiles`) then serve it as they serve a latent
page: one device operation a layer where keys and values apart would loop.
A prompt attends with its keys and values as they are. **The tails** are
what the next position needs of the ones before it: the last ``k0 - 1``
rows of ``u``, the last ``k1 - 1`` of ``c0`` and ``W_v2 h`` of the last
position, one row of the slot's one array a layer.

**The layers run under one ``lax.scan``** over their stacked weights (they
are all alike), so a program is compiled once a layer's worth. The experts
are not scanned over: all layers' lie in one stack ``[L E, D, 2F]`` that
the scan's body closes over, layer ``l``'s at ``l E ..``. A prompt's rows
are routed (:func:`experts.routed`, asked for expert ``l E + e*``: the
grouped kernel's weight tiles follow the expert id into the stack where it
lies, :mod:`demodel_tpu.ops.grouped`; ``lax.ragged_dot`` with empty groups
elsewhere); a step's few rows go through every expert of the layer in two
plain products that read the layer's experts out of the stack (:func:`_moe`).
Nothing is copied out of the stack either way.

**A step is written for few device operations.** A traced window keeps
only so many (PERF.md, section 5), and this model's layer is light: its
step is the densest in operations a second of any the repository serves.
So: one kernel for everything of a step between a layer's projection and
its attention (:func:`_step_rows`, :mod:`demodel_tpu.ops.cca_mix`); no
sort, table of groups or unsort in a step; a layer's vectors one float32
row sliced once (:func:`vector_widths`); the rotary's tables made once a
program (:func:`_turns`); all layers' new positions written by one
``put_positions`` and all rows' tails by one select of the slots' array
(``kvcache.Whole``). ``tests/test_tpu_layout.py`` counts what is left.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from demodel_tpu.models import experts
from demodel_tpu.models.common import attend, refuse_unsupported, rms_norm
from demodel_tpu.models.hf_loader import Weights, setter, zeros
from demodel_tpu.ops import cca_mix
from demodel_tpu.utils.metrics import HUB, labeled

HUB.inc(labeled("gen_moe_assignments_total", held="zero"), 0)
HUB.inc("gen_cca_kv_bytes_total", 0)
HUB.inc("gen_state_bytes_total", 0)

#: the four vectors of a merge, in the order the stacked leaf holds them
MERGE = ("residual_scale", "residual_bias", "output_scale", "output_bias")
#: rows x experts up to which a layer computes every expert for every row
#: and past which it routes (a prompt): 64 x 16, the widest decode bucket
#: that was measured on the chip (PERF.md section 6, PR 49; at 128 rows the
#: dense products are 16 times the routed ones' and nobody has timed them)
DENSE = 1024


@dataclass(frozen=True)
class ZayaConfig:
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5e6
    num_experts: int = 16
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    rms_norm_eps: float = 1e-5
    dtype: str = "float32"

    @property
    def q_dim(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        """The columns of a position's keys, and of its values."""
        return self.num_key_value_heads * self.head_dim

    @property
    def mixed(self) -> int:
        """The columns the two convolutions mix: ``[q~ ; k~]``."""
        return self.q_dim + self.kv_dim

    @property
    def page_dim(self) -> int:
        """What a position keeps a layer: ``[v | k^]``."""
        return 2 * self.kv_dim

    @property
    def tail_dim(self) -> int:
        """What a sequence keeps a layer beside its pages: the last ``k0 -
        1`` rows of ``u``, the last ``k1 - 1`` of ``c0``, ``W_v2 h`` of the
        last position."""
        return (self.cca_time0 + self.cca_time1 - 2) * self.mixed \
            + self.kv_dim // 2

    @property
    def rotary(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @classmethod
    def tiny(cls, **over) -> "ZayaConfig":
        """Test-sized: three layers, 4 heads over 2 of 16, a router of 16
        columns over 4 experts of 32 and the skip."""
        kw = dict(vocab_size=256, hidden_size=64, num_hidden_layers=3,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  num_experts=4, moe_intermediate_size=32,
                  router_hidden_size=16, rope_theta=10000.0)
        kw.update(over)
        return cls(**kw)

    @classmethod
    def from_hf(cls, config: dict) -> "ZayaConfig":
        """From a ``config.json`` under the published keys; a key whose
        value this module does not implement is refused by name. The
        family's switches that the 8B's shortened config leaves out are on
        where they are not stated."""
        rope = (config.get("rope_parameters") or {}).get("hybrid") or {}
        refuse_unsupported(
            config, fields=("attention_bias", "sliding_window",
                            "rope_scaling", "lm_head_bias"),
            only={"num_experts_per_tok": 1, "moe_router_topk": 1,
                  "hidden_act": "silu", "tie_word_embeddings": True,
                  "cca": True, "zaya_use_eda": True, "zaya_use_mod": True,
                  "scale_residual_merge": True,
                  "rope_parameters.rope_type": "default"})
        layers = int(config["num_hidden_layers"])
        kinds = set((config.get("layer_types") or ["hybrid"])[:layers])
        if kinds != {"hybrid"} or rope.get("rope_type", "default") != "default":
            raise ValueError(f"config field layer_types={sorted(kinds)!r} / "
                             f"rope_parameters.hybrid={rope!r} is not "
                             "supported by this stack")
        cfg = cls(
            vocab_size=int(config["vocab_size"]),
            hidden_size=int(config["hidden_size"]),
            num_hidden_layers=layers,
            num_attention_heads=int(config["num_attention_heads"]),
            num_key_value_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            cca_time0=int(config.get("cca_time0", 2)),
            cca_time1=int(config.get("cca_time1", 2)),
            partial_rotary_factor=float(rope.get(
                "partial_rotary_factor",
                config.get("partial_rotary_factor", 0.5))),
            rope_theta=float(rope.get("rope_theta",
                                      config.get("rope_theta", 5e6))),
            num_experts=int(config["num_experts"]),
            moe_intermediate_size=int(config["moe_intermediate_size"]),
            router_hidden_size=int(config["router_hidden_size"]),
            rms_norm_eps=float(config.get("rms_norm_eps", 1e-5)),
            dtype=(config.get("torch_dtype") or config.get("dtype")
                   or "float32"),
        )
        H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
        if H % Hkv or Hkv % 2 or cfg.rotary % 2 \
                or min(cfg.cca_time0, cfg.cca_time1) < 1:
            raise ValueError(
                f"config fields num_attention_heads={H}, "
                f"num_key_value_heads={Hkv}, partial_rotary_factor="
                f"{cfg.partial_rotary_factor}, cca_time0={cfg.cca_time0}, "
                f"cca_time1={cfg.cca_time1} are not supported by this "
                "stack (query heads in whole groups over an even number of "
                "KV heads, an even count of rotary columns, kernels of at "
                "least one position)")
        return cfg


# ------------------------------------------------------------------ params


def init_params(key, cfg: ZayaConfig) -> dict:
    """Seeded N(0, 1/fan_in) matrices (the embedding at the fan-in of the
    head it is, a block of ``W1`` at ``hd k1``), ``w0`` of ones, ones for
    norms, temperatures and the merges' scales, zeros for every bias and
    for ``gamma`` (the benchmark's family draws ``w0`` and ``gamma`` from
    its seed and fills two matrices otherwise, its ``tensors``): the tree
    :func:`load_params` builds. Every leaf of
    ``layers`` is stacked over the layers, and the experts of all layers
    are one stack, layer ``l``'s at ``l E ..``."""
    dt = jnp.dtype(cfg.dtype)
    D, R, F = cfg.hidden_size, cfg.router_hidden_size, \
        cfg.moe_intermediate_size
    L, E, C = cfg.num_hidden_layers, cfg.num_experts, cfg.mixed
    heads, hd = C // cfg.head_dim, cfg.head_dim
    k0, k1 = cfg.cca_time0, cfg.cca_time1
    keys = iter(jax.random.split(key, 16))

    def dense(*shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    def ones(*shape):
        return jnp.ones(shape, dt)

    def nought(*shape):
        return jnp.zeros(shape, dt)

    return {
        "embed": dense(cfg.vocab_size, D, fan_in=D),
        "layers": {
            "qkv": dense(L, D, C + cfg.kv_dim, fan_in=D),
            "conv1_w": dense(L, k1, heads, hd, hd, fan_in=hd * k1),
            "o_proj": dense(L, cfg.q_dim, D, fan_in=cfg.q_dim),
            "router_down": dense(L, D, R, fan_in=D),
            "router_w1": dense(L, R, R, fan_in=R),
            "router_w2": dense(L, R, R, fan_in=R),
            "router_w3": dense(L, R, E + 1, fan_in=R),
            "vectors": pack({
                name: (ones if name.endswith(("norm", "scale", "temp"))
                       or name.startswith("conv0_w") else nought)(L, n)
                for name, n in vector_widths(cfg).items()}, cfg),
        },
        "experts_gate_up": dense(L * E, D, 2 * F, fan_in=D),
        "experts_down": dense(L * E, F, D, fan_in=F),
        "final_norm": ones(D),
    }


def vector_widths(cfg: ZayaConfig) -> dict[str, int]:
    """A layer's vectors, in the order its one float32 row ``vectors``
    holds them: the two norms' weights, the two merges' four vectors each
    (``<merge>.<part>``, :data:`MERGE`), the depthwise convolution a
    position of its kernel (``conv0_w.<j>``), the two convolutions' biases
    and ``temp``, a factor a mixed column (one under the query heads, a key
    head's learned temperature under its columns), then the router's
    ``gamma``, its norm's weight, its MLP's biases and ``beta``. One leaf,
    so that a layer of the scan slices the stack once for them all; float32
    and flat, so that nothing converts or reshapes them a layer; the
    convolutions' side by side, as :func:`_step_rows` hands them on."""
    D, C, R = cfg.hidden_size, cfg.mixed, cfg.router_hidden_size
    E = cfg.num_experts
    return {"attn_norm": D, "mlp_norm": D,
            **{f"{merge}.{part}": D for merge in ("attn_merge", "mlp_merge")
               for part in MERGE},
            **{f"conv0_w.{j}": C for j in range(cfg.cca_time0)},
            "conv0_b": C, "conv1_b": C, "temp": C,
            "router_gamma": R, "router_norm": R, "router_b1": R,
            "router_b2": R, "router_b3": E + 1, "router_bias": E + 1}


def pack(named: dict, cfg: ZayaConfig):
    """Every layer's vectors ``named[name]`` [L, width] → ``vectors`` [L,
    total] float32."""
    return jnp.concatenate([named[name].astype(jnp.float32)
                            for name in vector_widths(cfg)], axis=1)


def unpack(vectors, cfg: ZayaConfig) -> dict:
    """``vectors`` [..., total] → its parts by name, [..., width] each, and
    ``mixing``: the convolutions' (taps, biases, ``temp``) as they lie side
    by side, [..., k0 + 3, C]."""
    out, at = {}, 0
    for name, n in vector_widths(cfg).items():
        if name == "conv0_w.0":     # the taps, the two biases, temp
            out["mixing"] = vectors[
                ..., at:at + (cfg.cca_time0 + 3) * cfg.mixed].reshape(
                *vectors.shape[:-1], cfg.cca_time0 + 3, cfg.mixed)
        out[name] = vectors[..., at:at + n]
        at += n
    return out


def param_shardings(cfg: ZayaConfig, mesh: Mesh) -> dict:
    """NamedSharding tree matching :func:`init_params`: the experts' stack
    split over ``ep`` (when it divides), everything else replicated."""
    rep = NamedSharding(mesh, P())
    tree = jax.tree.map(lambda _leaf: rep, jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)))
    n = experts.ep_size(mesh)
    if n > 1 and (cfg.num_hidden_layers * cfg.num_experts) % n == 0:
        tree["experts_gate_up"] = tree["experts_down"] = NamedSharding(
            mesh, P("ep"))
    return tree


from_hf = ZayaConfig.from_hf
#: served through its step functions only
forward = None


def load_params(weights: dict, cfg: ZayaConfig, mesh=None) -> dict:
    """The tree of :func:`init_params` from a checkpoint of ``[out, in]``
    matrices under the names ``benchmark/lib/families/zaya.py`` lists (the
    configuration's ``assumed_note`` says which of them are this
    repository's): ``self_attn.{q,k,v}_proj`` side by side as one matrix
    (``v_proj``'s first half of rows is ``W_v1``), ``conv_qk.0`` the
    depthwise convolution ``[C, 1, k0]``, ``conv_qk.1`` the grouped one
    ``[C, hd, k1]``, the router's ``depth_scale`` (``gamma``; layer 0 has
    none) and ``balancing_bias`` (``beta``, zero where the checkpoint has
    none). The experts of all layers are set into one stack in place, each
    matrix popped and freed, so boot holds the stack and one matrix."""
    w = Weights(weights)
    sh = param_shardings(cfg, mesh) if mesh is not None else {}
    lsh = sh.get("layers", {})
    L, E = cfg.num_hidden_layers, cfg.num_experts
    D, F, R = cfg.hidden_size, cfg.moe_intermediate_size, \
        cfg.router_hidden_size
    heads, hd = cfg.mixed // cfg.head_dim, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)

    def placed(leaf, out):
        return jax.device_put(out, lsh[leaf]) if lsh else out

    def stack(leaf, make):
        return placed(leaf, jnp.stack(
            [make(f"layers.{li}.", li) for li in range(L)]).astype(dt))

    def lin(name):
        return lambda p, _li: w.get(p + name + ".weight", transpose=True)

    def vec(name):
        return lambda p, _li: w.get(p + name)

    def optional(name, width):
        return lambda p, _li: w.get(p + name) if w.has(p + name) \
            else jnp.zeros((width,), dt)

    a, r = "self_attn.", "mlp.router."
    named = {
        "attn_norm": vec("input_layernorm.weight"),
        "mlp_norm": vec("post_attention_layernorm.weight"),
        **{f"{ours}.{part}": vec(f"{theirs}.{part}")
           for ours, theirs in (("attn_merge", "self_attn_merge"),
                                ("mlp_merge", "mlp_merge"))
           for part in MERGE},
        "conv0_b": vec(a + "conv_qk.0.bias"),
        "conv1_b": vec(a + "conv_qk.1.bias"),
        "router_gamma": optional(r + "depth_scale", R),
        "router_norm": vec(r + "norm.weight"),
        **{f"router_b{i + 1}": vec(f"{r}mlp.{i}.bias") for i in range(3)},
        "router_bias": optional(r + "balancing_bias", E + 1),
        # one under the query heads, a key head's temperature under its own
        "temp": lambda p, _li: jnp.concatenate(
            [jnp.ones((cfg.q_dim,), dt), jnp.repeat(w.get(p + a + "temp"),
                                                    hd)]),
    }
    # [C, 1, k0]: a position of the kernel a vector
    taps = [w.get(f"layers.{li}.{a}conv_qk.0.weight")[:, 0]
            for li in range(L)]
    stacked = {name: jnp.stack([make(f"layers.{li}.", li)
                                for li in range(L)])
               for name, make in named.items()}
    stacked.update({f"conv0_w.{j}": jnp.stack([t[:, j] for t in taps])
                    for j in range(cfg.cca_time0)})
    layers = {
        "qkv": stack("qkv", lambda p, _li: jnp.concatenate(
            [w.get(f"{p}{a}{x}_proj.weight", transpose=True)
             for x in "qkv"], axis=1)),
        # [C, hd in, k1] -> [k1, heads, hd in, hd out]
        "conv1_w": stack("conv1_w", lambda p, _li: w.get(
            p + a + "conv_qk.1.weight").reshape(heads, hd, hd, -1)
            .transpose(3, 0, 2, 1)),
        "o_proj": stack("o_proj", lin(a + "o_proj")),
        "router_down": stack("router_down", lin(r + "down_proj")),
        **{f"router_w{i + 1}": stack(f"router_w{i + 1}",
                                     lin(f"{r}mlp.{i}"))
           for i in range(3)},
        "vectors": placed("vectors", pack(stacked, cfg)),
    }

    def held(projs, shape, sharding):
        """All layers' experts of ``projs``, side by side, in one stack."""
        out = zeros(shape, cfg.dtype, sharding)()
        put = setter(sharding)
        for li in range(L):
            for e in range(E):
                for i, x in enumerate(projs):
                    out = put(out, w.get(
                        f"layers.{li}.mlp.experts.{e}.{x}_proj.weight"),
                        li * E + e, i * F)
        return out

    return {
        "embed": w.get("embed_tokens.weight", sharding=sh.get("embed")),
        "layers": layers,
        "experts_gate_up": held(("gate", "up"), (L * E, D, 2 * F),
                                sh.get("experts_gate_up")),
        "experts_down": held(("down",), (L * E, F, D),
                             sh.get("experts_down")),
        "final_norm": w.get("norm.weight", sharding=sh.get("final_norm")),
    }


# ------------------------------------------------- the attention sublayer


def _turns(positions, cfg: ZayaConfig):
    """The rotary of ``positions`` [B, T] as three tables a position, [3, N,
    hd] float32, computed once a program: a head's turned columns are ``x *
    c + roll(x, r/2) * s1 + roll(x, -r/2) * s2`` (rotate-half within the
    first ``r`` columns; ``c`` is one and the others zero past them)."""
    r, hd = cfg.rotary, cfg.head_dim
    inv = 1.0 / cfg.rope_theta ** (np.arange(0, r, 2, dtype=np.float32) / r)
    ang = positions.reshape(-1, 1).astype(jnp.float32) * inv  # [N, r/2]
    cos, sin, zero = jnp.cos(ang), jnp.sin(ang), jnp.zeros_like(ang)
    rest = jnp.zeros((ang.shape[0], hd - r), jnp.float32)
    return jnp.stack([
        jnp.concatenate([cos, cos, rest + 1.0], axis=1),
        jnp.concatenate([zero, sin, rest], axis=1),
        jnp.concatenate([-sin, zero, rest], axis=1)])


def _rotate(x, turns, cfg: ZayaConfig):
    """``x`` [N, h, hd] (float32) under ``turns`` (:func:`_turns`)."""
    c, s1, s2 = (t[:, None, :] for t in turns)
    half = cfg.rotary // 2
    return x * c + jnp.roll(x, half, axis=-1) * s1 \
        + jnp.roll(x, -half, axis=-1) * s2


def _mix(w, u, tails, cfg: ZayaConfig):
    """The two convolutions over ``u`` [B, T, C] → ``(c1 [B, T, C] float32,
    the rows of u and of c0 the next position needs)``. ``tails`` None: a
    sequence from its start, padded once by zeros of ``u``; else the last
    ``k0 - 1`` rows of ``u`` and ``k1 - 1`` of ``c0`` before these (a step).
    ``c0`` is rounded to the model's dtype, as the tail keeps it."""
    B, T, C = u.shape
    k0, k1 = cfg.cca_time0, cfg.cca_time1
    hd = cfg.head_dim
    f32 = jnp.float32

    def conv0(up, n):       # up holds n + k0 - 1 rows
        return (w["conv0_b"] + sum(
            w[f"conv0_w.{j}"] * up[:, j:j + n].astype(f32)
            for j in range(k0))).astype(u.dtype)

    if tails is None:
        up = jnp.pad(u, ((0, 0), (k0 + k1 - 2, 0), (0, 0)))
        c0 = conv0(up, T + k1 - 1)
    else:
        up = jnp.concatenate([tails[0], u], axis=1)
        c0 = jnp.concatenate([tails[1], conv0(up, T)], axis=1)
    heads = c0.reshape(B, T + k1 - 1, C // hd, hd)
    c1 = w["conv1_b"] + sum(
        jnp.einsum("bthd,hde->bthe", heads[:, j:j + T].astype(f32),
                   w["conv1_w"][j].astype(f32),
                   precision=lax.Precision.HIGHEST)
        for j in range(k1)).reshape(B, T, C)
    return c1, (up[:, up.shape[1] - (k0 - 1):],
                c0[:, c0.shape[1] - (k1 - 1):])


def _qk(w, u, c1, turns, cfg: ZayaConfig):
    """``u`` and its mixed ``c1`` → the queries [B, T, H, hd] and keys [B,
    T, Hkv, hd] attention runs on: the mean added, each head normalised to
    ``sqrt(hd)`` (the keys times their head's temperature) and rotated, in
    float32, then rounded. Query and key heads go through it as one
    array of positions flat, the keys' last."""
    B, T, C = u.shape
    H, Hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    N, f32 = B * T, jnp.float32
    heads = u.astype(f32).reshape(N, C // hd, hd)
    m_q = 0.5 * (heads[:, :H].reshape(N, Hkv, H // Hkv, hd)
                 + heads[:, H:, None])
    x = c1.reshape(N, C // hd, hd) + jnp.concatenate(
        [m_q.reshape(N, H, hd), m_q.mean(axis=2)], axis=1)
    # to a length of sqrt(hd); a zero vector stays zero
    x = x * lax.rsqrt(jnp.maximum((x * x).mean(axis=-1, keepdims=True),
                                  1e-30))
    x = _rotate(x * w["temp"].reshape(C // hd, hd), turns,
                cfg).astype(u.dtype)
    return x[:, :H].reshape(B, T, H, hd), x[:, H:].reshape(B, T, Hkv, hd)


def _plain_rows(w, qkv, tail, turns, cfg: ZayaConfig):
    """:func:`_step_rows` in ``jax.numpy``: the portable form, and the
    kernel's oracle."""
    N = qkv.shape[0]
    H, Hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    C, kv = cfg.mixed, cfg.kv_dim
    cuts = ((cfg.cca_time0 - 1) * C, (cfg.cca_time0 + cfg.cca_time1 - 2) * C)
    u = qkv[:, None, :C]
    c1, kept = _mix(w, u, (tail[:, :cuts[0]].reshape(N, -1, C),
                           tail[:, cuts[0]:cuts[1]].reshape(N, -1, C)), cfg)
    q, k = _qk(w, u, c1, turns, cfg)
    page = jnp.concatenate([qkv[:, C:C + kv // 2], tail[:, cuts[1]:],
                            k.reshape(N, kv)], axis=-1)
    # query head i lies under the columns of its own key head alone
    wide = jnp.einsum("njgd,jk->njgkd", q.reshape(N, Hkv, H // Hkv, hd),
                      jnp.eye(Hkv, dtype=q.dtype)).reshape(N, H, kv)
    wide = jnp.concatenate([jnp.zeros_like(wide), wide], axis=-1)
    return page, wide.reshape(N, H * 2 * kv), jnp.concatenate(
        [kept[0].reshape(N, -1), kept[1].reshape(N, -1),
         qkv[:, C + kv // 2:]], axis=-1)


def _step_rows(w, qkv, tail, turns, cfg: ZayaConfig):
    """A step's one position a row between the projection and the
    attention: ``qkv`` [N, C + kv] and the rows' ``tail`` [N, tail_dim] →
    ``(page rows [N, page], queries padded to the page's width [N, H *
    page], the tails the next step reads)``. In a program lowered for a TPU
    one kernel (:mod:`demodel_tpu.ops.cca_mix`, where a head is whole lane
    tiles of bfloat16); everywhere else, and as its oracle,
    :func:`_plain_rows`."""
    k0, k1 = cfg.cca_time0, cfg.cca_time1
    if cfg.head_dim % 128 or qkv.dtype != jnp.bfloat16:
        return _plain_rows(w, qkv, tail, turns, cfg)
    return lax.platform_dependent(
        qkv, tail, turns,
        tpu=lambda qkv, tail, turns: cca_mix.step_rows(
            qkv, tail, w["mixing"], w["conv1_w"], turns,
            H=cfg.num_attention_heads,
            Hkv=cfg.num_key_value_heads, k0=k0, k1=k1, rotary=cfg.rotary),
        default=lambda qkv, tail, turns: _plain_rows(w, qkv, tail, turns,
                                                     cfg))


def _attention(w, h, cfg: ZayaConfig, positions, turns, past, tail):
    """The attention over ``h`` [B, T, D] (normed) → ``(W_o out, the
    positions' page rows [B, T, 1, page], the tail [B, tail_dim])``.
    ``tail`` None (with ``past`` None): a prompt from its start, attended
    with keys and values as they are. Else a step: ``tail`` [B, tail_dim]
    the rows' tails, ``past`` the layer's pages, attended as one array
    under zero-padded queries."""
    B, T, _D = h.shape
    H, Hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    C, kv = cfg.mixed, cfg.kv_dim
    qkv = h @ w["qkv"]
    scale = hd ** -0.5
    with jax.named_scope("attn.cca.mix"):
        if tail is None:
            u = qkv[..., :C]
            c1, kept = _mix(w, u, None, cfg)
            q, k = _qk(w, u, c1, turns, cfg)
            # the values' second half is the position before's
            v = jnp.concatenate(
                [qkv[..., C:C + kv // 2],
                 jnp.pad(qkv[:, :-1, C + kv // 2:], ((0, 0), (1, 0), (0, 0)))],
                axis=-1)
            page = jnp.concatenate([v, k.reshape(B, T, kv)], axis=-1)
            new_tail = jnp.concatenate(
                [kept[0].reshape(B, -1), kept[1].reshape(B, -1),
                 qkv[:, -1, C + kv // 2:]], axis=-1)
        else:
            page, wide, new_tail = _step_rows(w, qkv[:, 0], tail, turns, cfg)
    if tail is None:
        out = attend(q, k, v.reshape(B, T, Hkv, hd), positions, scale=scale)
        page = page[:, :, None]
    else:
        page = page[:, None, None]
        o = attend(wide.reshape(B, 1, H, 2 * kv), page, page[..., :kv],
                   positions, past=past, scale=scale).reshape(
            B, 1, Hkv, H // Hkv, Hkv, hd)
        # a head keeps its own key head's values of those that come out
        own = np.eye(Hkv, dtype=bool)[:, None, :, None]
        out = jnp.where(own, o, 0).sum(axis=4).reshape(B, 1, H * hd)
    return out @ w["o_proj"], page, new_tail


def _norm(x, weight, cfg: ZayaConfig):
    """RMSNorm under a float32 ``weight``, rounded to ``x``'s dtype."""
    return rms_norm(x, weight, cfg.rms_norm_eps).astype(x.dtype)


def _merge(w, merge: str, x, y):
    """``s_r * (x + b_r) + s_o * (y + b_o)`` under the four vectors of
    ``merge``, in float32, rounded once."""
    s_r, b_r, s_o, b_o = (w[f"{merge}.{part}"] for part in MERGE)
    f32 = jnp.float32
    return (s_r * (x.astype(f32) + b_r)
            + s_o * (y.astype(f32) + b_o)).astype(x.dtype)


# ---------------------------------------------------- the expert sublayer


def route(w, g, rho, cfg: ZayaConfig):
    """``g`` [N, D] (normed) and the stream ``rho`` [N, R] of the layer
    before → ``(this layer's rho, chosen [N] of E + 1, its p [N])``: the
    down-projection, the depth average, a norm, the MLP, a softmax and the
    first maximum of ``p + beta``, in float32."""
    f32 = jnp.float32

    def lin(x, i):
        return jnp.dot(x, w[f"router_w{i}"].astype(f32),
                       precision=lax.Precision.HIGHEST) + w[f"router_b{i}"]

    rho = jnp.dot(g.astype(f32), w["router_down"].astype(f32),
                  precision=lax.Precision.HIGHEST) \
        + w["router_gamma"] * rho
    n = rms_norm(rho, w["router_norm"], cfg.rms_norm_eps)
    s = lin(jax.nn.gelu(lin(jax.nn.gelu(lin(n, 1), approximate=False), 2),
                        approximate=False), 3)
    p = jax.nn.softmax(s, axis=-1)
    chosen = jnp.argmax(p + w["router_bias"], axis=-1)
    mine = jnp.arange(p.shape[1])[None, :] == chosen[:, None]
    return rho, chosen, jnp.where(mine, p, 0.0).sum(axis=-1)


def _moe(w, stacks, li, g, rho, live, cfg: ZayaConfig, mesh):
    """The expert sublayer's sum for ``g`` [N, D] → ``(y [N, D], rho,
    the live tokens on each of the router's outputs [E + 1]: the experts',
    then the skips)``. Layer ``li``'s experts lie at ``li
    E ..`` of ``stacks``. **A step's few rows go through every expert of
    the layer** (:data:`DENSE`): 64 rows hit 15 or 16 of 16 experts anyway,
    the products cost nothing beside reading the weights, and sorting rows
    by expert, the groups' tables and the unsort are thirty device
    operations a layer that move nothing; a row keeps its own expert's
    result times ``p``, the skip's none. **A prompt's rows are routed**
    (:func:`experts.routed`): the grouped products are asked for expert
    ``li E + e*``, and the skip for an id past the stack, which nobody
    holds."""
    E, F = cfg.num_experts, cfg.moe_intermediate_size
    N, held = g.shape[0], stacks[1].shape[0]
    with jax.named_scope("moe.route"):
        rho, chosen, p = route(w, g, rho, cfg)
        # whose choice each of the router's outputs is, the skip's last
        mine = (jnp.arange(E + 1)[:, None] == chosen[None, :]) \
            & live[None, :]
    if N * E <= DENSE:
        with jax.named_scope("moe.experts"):
            gate_up, down = (lax.dynamic_slice_in_dim(a, li * E, E)
                             for a in stacks)
            h = jnp.einsum("nd,edf->enf", g, gate_up)
            # a row keeps its own expert's hidden columns, and the second
            # product sums over experts and columns at once
            h = jnp.where(mine[:E, :, None],
                          jax.nn.silu(h[..., :F]) * h[..., F:], 0)
            y = jnp.einsum("enf,efd->nd", h, down).astype(jnp.float32) \
                * p[:, None]
    else:
        with jax.named_scope("moe.route"):
            at = jnp.where(chosen < E, li * E + chosen, held)
            y, _tokens = experts.routed(
                g, live, at.astype(jnp.int32)[:, None], p[:, None], *stacks,
                0, mesh)
    with jax.named_scope("moe.skip"):
        counts = mine.sum(axis=1, dtype=jnp.int32)
    return y.astype(g.dtype), rho, counts


def _layer(carry, xs, cfg: ZayaConfig, stacks, positions, turns, live, past,
           mesh):
    """One layer, as ``lax.scan`` runs it: ``carry`` the two streams ``(x
    [B, T, D], rho [B T, R] float32)``, ``xs`` the layer's ``(weights,
    index, rows' tails or None)``; ``past(index)`` its pages (a step) or
    None (a prompt). Returns the streams and ``(page rows, tails, tokens
    on each of the router's outputs)``."""
    x, rho = carry
    w, li, tail = xs
    w = {**w, **unpack(w["vectors"], cfg)}
    B, T, D = x.shape
    with jax.named_scope("attn.cca"):
        a, page, new_tail = _attention(
            w, _norm(x, w["attn_norm"], cfg), cfg, positions, turns,
            past(li), tail)
    x = _merge(w, "attn_merge", x, a)
    g = _norm(x, w["mlp_norm"], cfg).reshape(B * T, D)
    y, rho, counts = _moe(w, stacks, li, g, rho, live.reshape(B * T), cfg,
                          mesh)
    x = _merge(w, "mlp_merge", x, y.reshape(B, T, D))
    return (x, rho), (page, new_tail, counts)


def _forward(params, tokens, cfg: ZayaConfig, positions, live, past, tails,
             mesh):
    """Every layer over ``tokens`` [B, T] under one scan → ``(x, pages [L,
    B, T, 1, page], tails [L, B, tail_dim], expert tokens [L, E], skips
    [L])``; ``tails`` the rows' [L, B, tail_dim] (a step) or None."""
    E = cfg.num_experts
    B, T = tokens.shape
    L = cfg.num_hidden_layers
    x = params["embed"][tokens]
    rho = jnp.zeros((B * T, cfg.router_hidden_size), jnp.float32)
    stacks = (params["experts_gate_up"], params["experts_down"])
    turns = _turns(positions, cfg)
    (x, _rho), (pages, tails, counts) = lax.scan(
        lambda carry, xs: _layer(carry, xs, cfg, stacks, positions, turns,
                                 live, past, mesh),
        (x, rho), (params["layers"], jnp.arange(L, dtype=jnp.int32), tails))
    return x, pages, tails, counts[:, :E], counts[:, E]


def _head(params, x, cfg: ZayaConfig):
    return _norm(x, params["final_norm"], cfg) @ params["embed"].T


# ------------------------------------------------------ the engine's steps


def cache_spec(cfg: ZayaConfig):
    """What the serving engine keeps for a sequence: every layer pages one
    vector a position, ``[v | k^]``, whose first ``kv_dim`` columns are its
    values (a page of one array), and every layer keeps a tail in the
    sequence's slot."""
    from demodel_tpu.serve.kvcache import CacheSpec

    L = cfg.num_hidden_layers
    return CacheSpec(L, 1, cfg.page_dim, values=cfg.kv_dim,
                     state=(("tail", (L, cfg.tail_dim), cfg.dtype),))


def step_prefill(params, tokens, cfg: ZayaConfig, mesh: Mesh | None = None):
    """``tokens`` [B, T] (equal lengths, from position 0) → ``(last_logits
    [B, V], written, expert_tokens, zero_tokens, moved)``: ``written``
    (``kvcache.Written``) every layer's page rows [L, B, T, 1, page] for
    the caller to page into the pool and every layer's tail for the slot;
    ``expert_tokens`` [L, E] and ``zero_tokens`` [L] (the skips) int32;
    ``moved`` int32 [2], the cached positions the step wrote of a layer and
    the tails it moved."""
    from demodel_tpu.serve import kvcache

    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    x, pages, tails, counts, skips = _forward(
        params, tokens, cfg, positions, jnp.ones((B, T), bool),
        lambda _li: None, None, mesh)
    return _head(params, x[:, -1], cfg), kvcache.Written(
        pages, {"tail": kvcache.Placed(tails)}), counts, skips, \
        jnp.asarray([B * T, B], jnp.int32)


def step_decode(params, tokens, cfg: ZayaConfig, cache, lengths,
                mesh: Mesh | None = None):
    """One decode step over a ragged batch: ``tokens`` [B], ``lengths`` [B]
    the filled prefix of each row (0 for a pad row of the bucket, which
    then chooses nothing and counts no skip), ``cache`` the engine's pool
    with the batch's block table and slots (``kvcache.Paged`` with no
    ``v``). Every layer reads all of its rows' pages (the rectangle up to
    two tiles a row, the tiles the rows have filled beyond) and its rows'
    tails, all layers' gathered once. Returns ``(logits [B, V], written,
    expert_tokens, zero_tokens, moved)`` like :func:`step_prefill`: the
    page rows [L, B, 1, 1, page] for the caller to write at ``lengths``,
    the slots' array with every live row's new tails in place
    (``kvcache.Whole``); ``moved`` the cached positions the rows read of a
    layer and the tails read and written."""
    from demodel_tpu.serve import kvcache

    filled = cache.filled(lengths)
    tiles = isinstance(filled, kvcache.Tiles)
    held = cache.state["tail"]              # [L, slots + 1, tail_dim]

    def past(li):
        return cache.past(li.astype(filled.ids.dtype) if tiles else li,
                          filled)

    x, pages, tails, counts, skips = _forward(
        params, tokens[:, None], cfg, lengths[:, None],
        (lengths > 0)[:, None], past,
        held.at[:, cache.slots].get(mode="promise_in_bounds"), mesh)
    # every live row's tails into its slot, all layers in one select: a
    # slot takes the one row that holds it (a product with a one-hot)
    mine = (jnp.arange(held.shape[1])[:, None] == cache.slots[None, :]) \
        & (lengths > 0)[None, :]
    held = jnp.where(mine.any(axis=1)[None, :, None], jnp.einsum(
        "sb,lbw->lsw", mine.astype(held.dtype), tails), held)
    moved = jnp.stack([lengths.sum(), 2 * (lengths > 0).sum()])
    return _head(params, x[:, 0], cfg), kvcache.Written(
        pages, {"tail": kvcache.Whole(held)}), counts, skips, \
        moved.astype(jnp.int32)


def observe(expert_tokens, zero_tokens, moved, tokens: int, cfg: ZayaConfig,
            platform: str = "cpu", rows: int = 0) -> dict:
    """A step's stats (on the host) and the tokens it ran → the span's
    attributes: ``assignments`` (one a token a layer), ``zero_tokens``
    (those that fell on the skip: held by nobody, absent from nobody,
    counted ``held="zero"``), the experts' as :func:`experts.observe` names
    them, ``cca_kv_bytes`` (the positions of the compressed page the step's
    rows read, a prefill: wrote, times the ``[v | k^]`` every layer keeps of
    one) and ``state_bytes`` (the tails read and written). ``expert_reads``
    is what it is for every family, the grouped kernel's visits: those of a
    program that routes its rows (a prompt's, lowered for a TPU), and 0 for
    one that computes every expert for every row (a step's: no grouped
    product, no kernel), which names ``experts_dense`` instead, the experts
    it read whole (every one of every layer). The counters are counted
    here."""
    L, itemsize = cfg.num_hidden_layers, jnp.dtype(cfg.dtype).itemsize
    positions, tails = (int(n) for n in np.asarray(moved))
    assignments = tokens * L
    free = int(zero_tokens.sum())
    kv = positions * L * cfg.page_dim * itemsize
    state = tails * L * cfg.tail_dim * itemsize
    # a program of few rows reads every expert of every layer once and
    # holds no grouped product (_moe)
    dense = rows * cfg.num_experts <= DENSE
    attrs = experts.observe(expert_tokens, assignments, free=free,
                            platform=platform, call_rows=rows,
                            grouped=not dense)
    if dense:
        attrs["experts_dense"] = L * cfg.num_experts
    HUB.inc(labeled("gen_moe_assignments_total", held="zero"), free)
    HUB.inc("gen_cca_kv_bytes_total", kv)
    HUB.inc("gen_state_bytes_total", state)
    return {"assignments": assignments, "zero_tokens": free, **attrs,
            "cca_kv_bytes": kv, "state_bytes": state}
