"""HF-checkpoint → params-tree mapping for the model families.

Consumes a flat ``{tensor_name: array}`` (a sink :class:`Placement`'s
arrays, or host numpy) holding a ``transformers``-layout state dict and
rebuilds each family's params pytree. torch ``nn.Linear`` stores
``[out, in]`` — those transpose on the way in; GPT-2's Conv1D already
stores ``[in, out]`` and loads verbatim. Optional name prefixes
("model.", "transformer.", "bert.") are stripped automatically.

A placed ``jax.Array`` never leaves the device: it enters the tree as it
is, or transposed where it lives. The loaders CONSUME the mapping — each
tensor is popped as it enters the tree — so a delivered weight is freed
as soon as its transposed copy exists and boot holds about one copy of
the model in HBM, not two.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from demodel_tpu.models import (axk1, exaone_moe, longcat_flash, phi4flash,
                                qwen3_next)
from demodel_tpu.models.bert import BertConfig
from demodel_tpu.models.gpt2 import GPT2Config
from demodel_tpu.models.llama import LlamaConfig, param_shardings

_PREFIXES = ("", "model.", "transformer.", "bert.")


@functools.lru_cache(maxsize=None)
def _placer(transpose: bool, sharding):
    """One jitted (transpose +) reshard per target layout — the layers of
    a model share it, so it compiles once per distinct weight shape."""
    return jax.jit((lambda x: x.T) if transpose else (lambda x: x),
                   out_shardings=sharding)


def _lay(arr, transpose: bool = False, sharding=None):
    """``arr`` as a tree leaf: under ``sharding`` (the model's own layout
    for this leaf) when given, else with the placement it arrived with."""
    if sharding is not None:
        return _placer(transpose, sharding)(arr)
    if not isinstance(arr, jax.Array):
        arr = jnp.asarray(arr)  # host numpy (tests, tools)
    return arr.T if transpose else arr


class _Weights:
    def __init__(self, weights: dict):
        self.w = weights

    def get(self, name: str, transpose: bool = False, sharding=None):
        for p in _PREFIXES:
            if p + name in self.w:
                return _lay(self.w.pop(p + name), transpose, sharding)
        raise KeyError(f"checkpoint has no tensor {name!r} "
                       f"(tried prefixes {_PREFIXES})")

    def has(self, name: str) -> bool:
        return any(p + name in self.w for p in _PREFIXES)


def load_llama_params(weights: dict, cfg: LlamaConfig, mesh=None) -> dict:
    """``mesh`` lays every leaf out as :func:`llama.param_shardings`
    wants it (column/row-parallel over ``tp``): the delivery plan's
    leading-axis shards are re-laid on the mesh's devices."""
    w = _Weights(weights)
    sh = param_shardings(cfg, mesh) if mesh is not None else {}
    layers = []
    for i in range(cfg.num_hidden_layers):
        pre = f"layers.{i}."
        lsh = sh["layers"][i] if sh else {}

        def lin(name, leaf):
            return w.get(pre + name, transpose=True, sharding=lsh.get(leaf))

        layers.append({
            "attn_norm": w.get(pre + "input_layernorm.weight",
                               sharding=lsh.get("attn_norm")),
            "q_proj": lin("self_attn.q_proj.weight", "q_proj"),
            "k_proj": lin("self_attn.k_proj.weight", "k_proj"),
            "v_proj": lin("self_attn.v_proj.weight", "v_proj"),
            "o_proj": lin("self_attn.o_proj.weight", "o_proj"),
            "mlp_norm": w.get(pre + "post_attention_layernorm.weight",
                              sharding=lsh.get("mlp_norm")),
            "gate_proj": lin("mlp.gate_proj.weight", "gate_proj"),
            "up_proj": lin("mlp.up_proj.weight", "up_proj"),
            "down_proj": lin("mlp.down_proj.weight", "down_proj"),
        })
    embed = w.get("embed_tokens.weight", sharding=sh.get("embed"))
    if w.has("lm_head.weight"):
        head = w.get("lm_head.weight", transpose=True,
                     sharding=sh.get("lm_head"))
    else:  # tied embeddings
        head = _lay(embed, True, sh.get("lm_head"))
    return {
        "embed": embed,
        "layers": layers,
        "final_norm": w.get("norm.weight", sharding=sh.get("final_norm")),
        "lm_head": head,
    }


@functools.lru_cache(maxsize=None)
def _setter(sharding):
    """Jitted, the stack donated: one expert's ``[out, in]`` matrix of one
    projection, transposed, into its place ``[e, :, at : at + out]`` of
    the stacked tensor."""
    def put(stack, w, e, at):
        return jax.lax.dynamic_update_slice(stack, w.T[None], (e, 0, at))

    return jax.jit(put, donate_argnums=0, out_shardings=sharding)


@functools.lru_cache(maxsize=None)
def _zeros(shape, dtype, sharding):
    return jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sharding)


def _stack_experts(w: "_Weights", pre: str, projs, cfg, sharding):
    """The held experts' ``<pre>mlp.experts.<e>.<p>_proj.weight`` (``[out,
    in]`` each, under their index in the whole layer) for the projections
    ``projs`` → one ``[E, in, len(projs) * out]``, a projection's runs side
    by side. Each matrix is popped, set into the stack in place and freed,
    so boot holds the stack and one matrix, not the experts twice."""
    D, F = cfg.hidden_size, cfg.moe_intermediate_size
    shape = (cfg.num_experts, *((F, D) if projs == ("down",)
                                else (D, len(projs) * F)))
    stack = _zeros(shape, cfg.dtype, sharding)()
    put = _setter(sharding)
    first = cfg.ep_rank * cfg.num_experts
    for j in range(cfg.num_experts):
        for i, p in enumerate(projs):
            stack = put(stack, w.get(
                f"{pre}mlp.experts.{first + j}.{p}_proj.weight"), j, i * F)
    return stack


def load_exaone_moe_params(weights: dict, cfg: "exaone_moe.ExaoneMoeConfig",
                           mesh=None) -> dict:
    """The tree of :func:`exaone_moe.init_params` from a checkpoint that
    holds one share of the experts under their global indices
    (``mlp.experts.<ep_rank * num_experts + j>``). Per-expert matrices are
    stacked, gate beside up, so that a projection is one grouped product;
    the router keeps its whole width. Tensors of the multi-token-prediction
    layer stay in ``weights``."""
    w = _Weights(weights)
    sh = exaone_moe.param_shardings(cfg, mesh) if mesh is not None else {}
    layers = []
    for i, sparse in enumerate(cfg.sparse):
        pre = f"layers.{i}."
        lsh = sh["layers"][i] if sh else {}

        def lin(name, leaf):
            return w.get(pre + name, transpose=True, sharding=lsh.get(leaf))

        def vec(name, leaf):
            return w.get(pre + name, sharding=lsh.get(leaf))

        def held(projs, leaf):
            return _stack_experts(w, pre, projs, cfg, lsh.get(leaf))

        layer = {
            "q_proj": lin("self_attn.q_proj.weight", "q_proj"),
            "k_proj": lin("self_attn.k_proj.weight", "k_proj"),
            "v_proj": lin("self_attn.v_proj.weight", "v_proj"),
            "o_proj": lin("self_attn.o_proj.weight", "o_proj"),
            "q_norm": vec("self_attn.q_norm.weight", "q_norm"),
            "k_norm": vec("self_attn.k_norm.weight", "k_norm"),
            "attn_norm": vec("post_attn_layernorm.weight", "attn_norm"),
            "mlp_norm": vec("post_feedforward_layernorm.weight", "mlp_norm"),
        }
        if sparse:
            layer.update({
                "router": lin("mlp.gate.weight", "router"),
                "router_bias": vec("mlp.gate.e_score_correction_bias",
                                   "router_bias").astype(jnp.float32),
                "experts_gate_up": held(("gate", "up"),
                                           "experts_gate_up"),
                "experts_down": held(("down",), "experts_down"),
                "shared_gate_proj": lin("mlp.shared_experts.gate_proj.weight",
                                        "shared_gate_proj"),
                "shared_up_proj": lin("mlp.shared_experts.up_proj.weight",
                                      "shared_up_proj"),
                "shared_down_proj": lin("mlp.shared_experts.down_proj.weight",
                                        "shared_down_proj"),
            })
        else:
            layer.update({
                "gate_proj": lin("mlp.gate_proj.weight", "gate_proj"),
                "up_proj": lin("mlp.up_proj.weight", "up_proj"),
                "down_proj": lin("mlp.down_proj.weight", "down_proj"),
            })
        layers.append(layer)
    return {
        "embed": w.get("embed_tokens.weight", sharding=sh.get("embed")),
        "layers": layers,
        "final_norm": w.get("norm.weight", sharding=sh.get("final_norm")),
        "lm_head": w.get("lm_head.weight", transpose=True,
                         sharding=sh.get("lm_head")),
    }


@functools.lru_cache(maxsize=None)
def _head_splitter(heads: int, first: int, by_head: bool, sharding):
    """Jitted: a ``[heads * (first + rest), in]`` matrix whose rows lie head
    by head, each head's ``first`` rows before its ``rest`` → the two
    parts, each ``[heads, in, width]`` (``by_head``) or ``[heads * width,
    in]`` (its rows, as they lay)."""
    def split(x):
        x = x.reshape(heads, -1, x.shape[1])
        parts = x[:, :first], x[:, first:]
        if by_head:
            return tuple(p.transpose(0, 2, 1) for p in parts)
        return tuple(p.reshape(-1, x.shape[2]) for p in parts)

    return jax.jit(split, out_shardings=(sharding, sharding))


@functools.lru_cache(maxsize=None)
def _folder(scale: float, sharding):
    """Jitted :func:`longcat_flash.fold` of one latent norm's weight."""
    return jax.jit(lambda w: longcat_flash.fold(w, scale),
                   out_shardings=sharding)


def _latent_attention(w: "_Weights", a: str, heads: int, nope: int,
                      sh: dict, scales: tuple = (None, None)) -> dict:
    """One latent attention's tensors under the prefix ``a`` → the nine
    leaves :mod:`demodel_tpu.models.latent` reads (``sh`` their shardings
    by leaf). ``kv_b_proj`` (a head's ``nope`` key rows before its value
    rows) is split by head into ``w_uk`` and ``w_uv``, ``q_b_proj`` (a
    head's ``nope`` unrotated rows before its rotary ones) into the two
    kinds of row. ``scales``: what a family multiplies the normalised
    ``c_q`` and ``c_kv`` by, folded into the two norms' weights in float32
    (None: the weight as the checkpoint holds it)."""
    def lin(name, leaf):
        return w.get(a + name, transpose=True, sharding=sh.get(leaf))

    def norm(name, leaf, scale):
        if scale is None:
            return w.get(a + name, sharding=sh.get(leaf))
        return _folder(scale, sh.get(leaf))(w.get(a + name))

    w_uk, w_uv = _head_splitter(heads, nope, True, sh.get("w_uk"))(
        w.get(a + "kv_b_proj.weight"))
    q_b_nope, q_b_rope = _head_splitter(
        heads, nope, False, sh.get("q_b_nope"))(w.get(a + "q_b_proj.weight"))
    return {
        "q_a_proj": lin("q_a_proj.weight", "q_a_proj"),
        "q_a_norm": norm("q_a_layernorm.weight", "q_a_norm", scales[0]),
        "q_b_nope": q_b_nope, "q_b_rope": q_b_rope,
        "kv_a_proj": lin("kv_a_proj_with_mqa.weight", "kv_a_proj"),
        "kv_a_norm": norm("kv_a_layernorm.weight", "kv_a_norm", scales[1]),
        "w_uk": w_uk, "w_uv": w_uv,
        "o_proj": lin("o_proj.weight", "o_proj"),
    }


def load_axk1_params(weights: dict, cfg: "axk1.AxK1Config",
                     mesh=None) -> dict:
    """The tree of :func:`axk1.init_params` from a checkpoint of the
    DeepSeek-V3 style of names, holding one share of the experts under
    their global indices. The attention is :func:`_latent_attention`'s
    (``w_uk`` and ``w_uv`` by head, which the expanded prefill and the
    absorbed decode both read); the experts are stacked as
    :func:`load_exaone_moe_params` stacks them. A selection
    bias in the checkpoint is refused: the module implements
    ``topk_method`` ``none``, which has none."""
    w = _Weights(weights)
    sh = axk1.param_shardings(cfg, mesh) if mesh is not None else {}
    layers = []
    for i in range(cfg.num_hidden_layers):
        pre = f"layers.{i}."
        lsh = sh["layers"][i] if sh else {}

        def lin(name, leaf):
            return w.get(pre + name, transpose=True, sharding=lsh.get(leaf))

        def vec(name, leaf):
            return w.get(pre + name, sharding=lsh.get(leaf))

        def held(projs, leaf):
            return _stack_experts(w, pre, projs, cfg, lsh.get(leaf))

        if w.has(pre + "mlp.gate.e_score_correction_bias"):
            raise ValueError(
                f"checkpoint tensor {pre}mlp.gate.e_score_correction_bias: "
                "a selection bias is not supported by this stack "
                "(topk_method none)")
        layer = {
            **_latent_attention(w, pre + "self_attn.",
                                cfg.num_attention_heads,
                                cfg.qk_nope_head_dim, lsh),
            "attn_norm": vec("input_layernorm.weight", "attn_norm"),
            "mlp_norm": vec("post_attention_layernorm.weight", "mlp_norm"),
        }
        if i >= cfg.first_k_dense_replace:
            layer.update({
                "router": lin("mlp.gate.weight", "router"),
                "experts_gate_up": held(("gate", "up"), "experts_gate_up"),
                "experts_down": held(("down",), "experts_down"),
                "shared_gate_proj": lin("mlp.shared_experts.gate_proj.weight",
                                        "shared_gate_proj"),
                "shared_up_proj": lin("mlp.shared_experts.up_proj.weight",
                                      "shared_up_proj"),
                "shared_down_proj": lin("mlp.shared_experts.down_proj.weight",
                                        "shared_down_proj"),
            })
        else:
            layer.update({
                "gate_proj": lin("mlp.gate_proj.weight", "gate_proj"),
                "up_proj": lin("mlp.up_proj.weight", "up_proj"),
                "down_proj": lin("mlp.down_proj.weight", "down_proj"),
            })
        layers.append(layer)
    return {
        "embed": w.get("embed_tokens.weight", sharding=sh.get("embed")),
        "layers": layers,
        "final_norm": w.get("norm.weight", sharding=sh.get("final_norm")),
        "lm_head": w.get("lm_head.weight", transpose=True,
                         sharding=sh.get("lm_head")),
    }


@functools.lru_cache(maxsize=None)
def _regrouper(groups: int, widths: tuple[int, ...], sharding):
    """Jitted: a ``[out, in]`` matrix whose rows lie in ``groups`` runs,
    each holding ``widths`` rows of the parts side by side, → ``[in, out]``
    with each part's rows of all runs together, the parts in order."""
    def regroup(x):
        x = x.reshape(groups, sum(widths), x.shape[1])
        at, parts = 0, []
        for n in widths:
            parts.append(x[:, at:at + n].reshape(groups * n, -1))
            at += n
        return jnp.concatenate(parts).T

    return jax.jit(regroup, out_shardings=sharding)


def load_qwen3_next_params(weights: dict,
                           cfg: "qwen3_next.Qwen3NextConfig",
                           mesh=None) -> dict:
    """The tree of :func:`qwen3_next.init_params` from a checkpoint that
    holds one share of the experts under their global indices. Hugging
    Face lays ``in_proj_qkvz`` and ``in_proj_ba`` out a key head at a time
    (``q | k | v | z`` of one head, then the next) and ``q_proj`` an
    attention head at a time (its query, then its gate): they are regrouped
    so that each part is one run of columns. Tensors of the
    multi-token-prediction layer stay in ``weights``."""
    w = _Weights(weights)
    sh = qwen3_next.param_shardings(cfg, mesh) if mesh is not None else {}
    Hk, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
    r = cfg.linear_num_value_heads // Hk
    rdv = r * cfg.linear_value_head_dim
    layers = []
    for i, full in enumerate(cfg.full):
        pre = f"layers.{i}."
        lsh = sh["layers"][i] if sh else {}

        def lin(name, leaf):
            return w.get(pre + name, transpose=True, sharding=lsh.get(leaf))

        def vec(name, leaf):
            return w.get(pre + name, sharding=lsh.get(leaf))

        def regrouped(name, leaf, groups, widths):
            return _regrouper(groups, widths, lsh.get(leaf))(
                w.get(pre + name))

        def held(projs, leaf):
            return _stack_experts(w, pre, projs, cfg, lsh.get(leaf))

        layer = {
            "in_norm": vec("input_layernorm.weight", "in_norm"),
            "post_norm": vec("post_attention_layernorm.weight", "post_norm"),
            "router": lin("mlp.gate.weight", "router"),
            "experts_gate_up": held(("gate", "up"), "experts_gate_up"),
            "experts_down": held(("down",), "experts_down"),
            "shared_gate_proj": lin("mlp.shared_expert.gate_proj.weight",
                                    "shared_gate_proj"),
            "shared_up_proj": lin("mlp.shared_expert.up_proj.weight",
                                  "shared_up_proj"),
            "shared_down_proj": lin("mlp.shared_expert.down_proj.weight",
                                    "shared_down_proj"),
            "shared_gate": lin("mlp.shared_expert_gate.weight",
                               "shared_gate"),
        }
        if full:
            layer.update({
                "q_proj": regrouped("self_attn.q_proj.weight", "q_proj",
                                    cfg.num_attention_heads,
                                    (cfg.head_dim, cfg.head_dim)),
                "k_proj": lin("self_attn.k_proj.weight", "k_proj"),
                "v_proj": lin("self_attn.v_proj.weight", "v_proj"),
                "o_proj": lin("self_attn.o_proj.weight", "o_proj"),
                "q_norm": vec("self_attn.q_norm.weight", "q_norm"),
                "k_norm": vec("self_attn.k_norm.weight", "k_norm"),
            })
        else:
            conv = w.get(pre + "linear_attn.conv1d.weight")     # [C, 1, K]
            layer.update({
                "in_proj_qkvz": regrouped(
                    "linear_attn.in_proj_qkvz.weight", "in_proj_qkvz", Hk,
                    (dk, dk, rdv, rdv)),
                "in_proj_ba": regrouped("linear_attn.in_proj_ba.weight",
                                        "in_proj_ba", Hk, (r, r)),
                "conv": _lay(conv.reshape(conv.shape[0], conv.shape[-1]),
                             True, lsh.get("conv")),
                "A_log": vec("linear_attn.A_log", "A_log"),
                "dt_bias": vec("linear_attn.dt_bias", "dt_bias"),
                "gdn_norm": vec("linear_attn.norm.weight", "gdn_norm"),
                "out_proj": lin("linear_attn.out_proj.weight", "out_proj"),
            })
        layers.append(layer)
    return {
        "embed": w.get("embed_tokens.weight", sharding=sh.get("embed")),
        "layers": layers,
        "final_norm": w.get("norm.weight", sharding=sh.get("final_norm")),
        "lm_head": w.get("lm_head.weight", transpose=True,
                         sharding=sh.get("lm_head")),
    }


def load_longcat_flash_params(weights: dict,
                              cfg: "longcat_flash.LongcatFlashConfig",
                              mesh=None) -> dict:
    """The tree of :func:`longcat_flash.init_params` from a checkpoint of
    the LongCat-Flash style of names (a layer's two sublayers under
    ``self_attn.<i>``, ``input_layernorm.<i>``,
    ``post_attention_layernorm.<i>`` and ``mlps.<i>``, its expert layer
    under ``mlp``), holding one share of the routed experts under their
    global indices. Each attention is :func:`_latent_attention`'s, with the
    two scales of the latent norms' outputs (``mla_scale_q_lora``,
    ``mla_scale_kv_lora``) folded into ``q_a_layernorm`` and
    ``kv_a_layernorm``. The router
    keeps its whole width, identity experts included; a selection bias is
    taken where the checkpoint has one and is zero where not."""
    w = _Weights(weights)
    sh = longcat_flash.param_shardings(cfg, mesh) if mesh is not None else {}
    layers = []
    for li in range(cfg.num_layers):
        pre = f"layers.{li}."
        lsh = sh["layers"][li] if sh else {}

        def sublayer(i: int) -> dict:
            ssh = lsh["sub"][i] if lsh else {}
            return {
                "attn": _latent_attention(
                    w, f"{pre}self_attn.{i}.", cfg.num_attention_heads,
                    cfg.qk_nope_head_dim, ssh.get("attn", {}),
                    cfg.latent_scales),
                "attn_norm": w.get(f"{pre}input_layernorm.{i}.weight",
                                   sharding=ssh.get("attn_norm")),
                "mlp_norm": w.get(
                    f"{pre}post_attention_layernorm.{i}.weight",
                    sharding=ssh.get("mlp_norm")),
                **{f"{x}_proj": w.get(f"{pre}mlps.{i}.{x}_proj.weight",
                                      transpose=True,
                                      sharding=ssh.get(f"{x}_proj"))
                   for x in ("gate", "up", "down")},
            }

        bias = pre + "mlp.router.e_score_correction_bias"
        layers.append({
            "sub": [sublayer(0), sublayer(1)],
            "router": w.get(pre + "mlp.router.classifier.weight",
                            transpose=True, sharding=lsh.get("router")),
            "router_bias": w.get(
                bias, sharding=lsh.get("router_bias")).astype(jnp.float32)
            if w.has(bias) else _zeros((cfg.router_width,), "float32",
                                       lsh.get("router_bias"))(),
            "experts_gate_up": _stack_experts(
                w, pre, ("gate", "up"), cfg, lsh.get("experts_gate_up")),
            "experts_down": _stack_experts(
                w, pre, ("down",), cfg, lsh.get("experts_down")),
        })
    return {
        "embed": w.get("embed_tokens.weight", sharding=sh.get("embed")),
        "layers": layers,
        "final_norm": w.get("norm.weight", sharding=sh.get("final_norm")),
        "lm_head": w.get("lm_head.weight", transpose=True,
                         sharding=sh.get("lm_head")),
    }


def load_phi4flash_params(weights: dict,
                          cfg: "phi4flash.Phi4FlashConfig",
                          mesh=None) -> dict:
    """The tree of :func:`phi4flash.init_params` (the layers grouped and
    stacked by :func:`phi4flash.stack_layers`). Every layer's mixer is
    ``attn`` in the checkpoint, whatever its kind. An attention layer's
    ``Wqkv`` (queries, then keys, then values) enters as the query columns
    and the key and value columns apart, so that a prefill can make keys
    for a whole prompt and a query for its last position; the head is the
    embedding, which the tree holds once."""
    w = _Weights(weights)
    # tp shards nothing of this family: every leaf is laid replicated
    rep = NamedSharding(mesh, PartitionSpec()) if mesh is not None else None
    nq = cfg.num_attention_heads * cfg.head_dim
    layers = []
    for i, kind in enumerate(cfg.kinds):
        pre = f"layers.{i}."

        def lin(name):
            return w.get(pre + name, transpose=True, sharding=rep)

        def vec(name):
            return w.get(pre + name, sharding=rep)

        layer = {
            "ln1_w": vec("input_layernorm.weight"),
            "ln1_b": vec("input_layernorm.bias"),
            "ln2_w": vec("post_attention_layernorm.weight"),
            "ln2_b": vec("post_attention_layernorm.bias"),
            "fc1": lin("mlp.fc1.weight"),
            "fc2": lin("mlp.fc2.weight"),
        }
        if kind == "mamba":
            conv = w.get(pre + "attn.conv1d.weight")        # [Dn, 1, K]
            layer.update({
                "in_proj": lin("attn.in_proj.weight"),
                "conv_w": _lay(conv.reshape(conv.shape[0], conv.shape[-1]),
                               True, rep),
                "conv_b": vec("attn.conv1d.bias"),
                "x_proj": lin("attn.x_proj.weight"),
                "dt_proj": lin("attn.dt_proj.weight"),
                "dt_bias": vec("attn.dt_proj.bias"),
                "A_log": vec("attn.A_log"),
                "D": vec("attn.D"),
                "out_proj": lin("attn.out_proj.weight"),
            })
        elif kind == "gmu":
            layer.update({
                "in_proj": lin("attn.in_proj.weight"),
                "out_proj": lin("attn.out_proj.weight"),
            })
        else:
            wqkv = w.get(pre + "attn.Wqkv.weight")
            bqkv = w.get(pre + "attn.Wqkv.bias")
            layer.update({"wq": _lay(wqkv[:nq], True, rep),
                          "bq": _lay(bqkv[:nq], sharding=rep)})
            if kind != "cross":
                layer.update({"wkv": _lay(wqkv[nq:], True, rep),
                              "bkv": _lay(bqkv[nq:], sharding=rep)})
            layer.update({
                "out_proj": lin("attn.out_proj.weight"),
                "out_bias": vec("attn.out_proj.bias"),
                "subln": vec("attn.inner_cross_attn.subln.weight"),
                **{f"lambda_{x}": vec(f"attn.inner_cross_attn.lambda_{x}")
                   for x in ("q1", "k1", "q2", "k2")}})
        layers.append(layer)
    return {
        "embed": w.get("embed_tokens.weight", sharding=rep),
        "final_ln_w": w.get("final_layernorm.weight", sharding=rep),
        "final_ln_b": w.get("final_layernorm.bias", sharding=rep),
        **phi4flash.stack_layers(layers, cfg),
    }


def load_gpt2_params(weights: dict, cfg: GPT2Config) -> dict:
    w = _Weights(weights)
    layers = []
    for i in range(cfg.n_layer):
        pre = f"h.{i}."
        layers.append({
            "ln_1": {"w": w.get(pre + "ln_1.weight"),
                     "b": w.get(pre + "ln_1.bias")},
            "c_attn": {"w": w.get(pre + "attn.c_attn.weight"),
                       "b": w.get(pre + "attn.c_attn.bias")},
            "c_proj": {"w": w.get(pre + "attn.c_proj.weight"),
                       "b": w.get(pre + "attn.c_proj.bias")},
            "ln_2": {"w": w.get(pre + "ln_2.weight"),
                     "b": w.get(pre + "ln_2.bias")},
            "mlp_fc": {"w": w.get(pre + "mlp.c_fc.weight"),
                       "b": w.get(pre + "mlp.c_fc.bias")},
            "mlp_proj": {"w": w.get(pre + "mlp.c_proj.weight"),
                         "b": w.get(pre + "mlp.c_proj.bias")},
        })
    return {
        "wte": w.get("wte.weight"),
        "wpe": w.get("wpe.weight"),
        "layers": layers,
        "ln_f": {"w": w.get("ln_f.weight"), "b": w.get("ln_f.bias")},
    }


def load_bert_params(weights: dict, cfg: BertConfig) -> dict:
    w = _Weights(weights)

    def lin(name):
        return {"w": w.get(name + ".weight", transpose=True),
                "b": w.get(name + ".bias")}

    def ln(name):
        return {"w": w.get(name + ".weight"), "b": w.get(name + ".bias")}

    layers = []
    for i in range(cfg.num_hidden_layers):
        pre = f"encoder.layer.{i}."
        layers.append({
            "q": lin(pre + "attention.self.query"),
            "k": lin(pre + "attention.self.key"),
            "v": lin(pre + "attention.self.value"),
            "attn_out": lin(pre + "attention.output.dense"),
            "attn_ln": ln(pre + "attention.output.LayerNorm"),
            "inter": lin(pre + "intermediate.dense"),
            "out": lin(pre + "output.dense"),
            "out_ln": ln(pre + "output.LayerNorm"),
        })
    return {
        "word_emb": w.get("embeddings.word_embeddings.weight"),
        "pos_emb": w.get("embeddings.position_embeddings.weight"),
        "type_emb": w.get("embeddings.token_type_embeddings.weight"),
        "emb_ln": ln("embeddings.LayerNorm"),
        "layers": layers,
    }
