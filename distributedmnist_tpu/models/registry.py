"""Model registry: every model is a pure (init, apply, loss, accuracy)
bundle over a param pytree — no classes, no hidden state, trivially
compatible with jit/grad/shard_map.

Replaces the reference's single hardwired model module
(src/mnist.py, wired at src/distributed_train.py:158-171) with a
family registry covering the BASELINE.json configs (MNIST CNN,
Fashion-MNIST CNN, CIFAR-10 ResNet-20, plus a transformer for the
long-context path).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..core.config import ModelConfig

# NOTE: rule tables reference parallel/partition_rules.py only by
# convention (they receive its RuleAxes and return its (regex, spec)
# Rule pairs) — importing it here would cycle through parallel/__init__
# → parallel.api → models.registry.


def classification_eval_metrics(logits: jax.Array, labels: jax.Array,
                                weight: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-batch weighted eval sums for a [batch, classes] classifier:
    (correct_sum, loss_sum, weight_sum). Padded examples carry weight 0
    so they never bias metrics."""
    w = weight.astype(jnp.float32)
    correct = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                               axis=-1)[:, 0]
    return jnp.sum(correct * w), jnp.sum(nll * w), jnp.sum(w)


def classification_predictions(logits: jax.Array) -> jax.Array:
    """Softmax class probabilities [batch, classes] — the inference
    export every classifier serves (≙ cnn.predictions; defined here so
    EVERY registered model carries one and ``servesvc`` stays
    model-agnostic the way the trainer is)."""
    return jax.nn.softmax(logits, axis=-1)


def lm_last_logits(logits: jax.Array) -> jax.Array:
    """Last-position logits [batch, vocab] of a [batch, seq, vocab]
    causal-LM forward — the shared next-token head every LM consumer
    (the one-shot ``lm_predictions`` export, the decode service's
    prefill) reads instead of each re-spelling the slice."""
    return logits[:, -1]


def lm_predictions(logits: jax.Array) -> jax.Array:
    """Next-token distribution [batch, vocab] for a causal LM: softmax
    over the last position's logits (:func:`lm_last_logits`) — the
    one-shot inference export (what the classification-shaped serving
    path ranks from)."""
    return jax.nn.softmax(lm_last_logits(logits), axis=-1)


def sample_token(logits: jax.Array, key: jax.Array | None = None,
                 temperature: float = 0.0,
                 top_k: int = 0) -> jax.Array:
    """Sample next-token ids [...] from logits [..., vocab].

    ``temperature <= 0`` is greedy argmax — deterministic, no key
    needed (and the limit temperature → 0 of the sampled path, pinned
    in tests). ``temperature > 0`` divides the logits before a
    categorical draw; ``top_k > 0`` additionally masks everything
    below the k-th highest logit (top_k=1 ≡ greedy)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if key is None:
        raise ValueError("sample_token with temperature > 0 needs a "
                         "PRNG key")
    scaled = logits.astype(jnp.float32) / temperature
    if top_k and top_k > 0:
        kth = jnp.sort(scaled, axis=-1)[..., -top_k][..., None]
        scaled = jnp.where(scaled < kth, -1e30, scaled)
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


def lm_eval_metrics(logits: jax.Array, labels: jax.Array,
                    weight: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Token-level eval sums for a [batch, seq, vocab] causal LM
    (weight is per-sequence; counts are per predicted token)."""
    w = weight.astype(jnp.float32)[:, None]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    tgt = labels[:, 1:].astype(jnp.int32)
    correct = (jnp.argmax(logp, axis=-1) == tgt).astype(jnp.float32)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return (jnp.sum(correct * w), jnp.sum(nll * w),
            jnp.sum(w * jnp.ones_like(correct)))


@dataclasses.dataclass(frozen=True)
class Model:
    """A model family instance.

    * ``init(key) -> params``
    * ``apply(params, inputs, train=..., dropout_key=...) -> logits``
    * ``loss(logits, labels) -> scalar``
    * ``accuracy(logits, labels) -> scalar``
    * ``eval_metrics(logits, labels, weight) -> (correct_sum, loss_sum, weight_sum)``
    * ``input_shape`` excludes the batch dim.
    """

    name: str
    init: Callable[[jax.Array], Any]
    apply: Callable[..., jax.Array]
    loss: Callable[[jax.Array, jax.Array], jax.Array]
    accuracy: Callable[[jax.Array, jax.Array], jax.Array]
    input_shape: tuple[int, ...]
    input_dtype: Any = jnp.float32
    eval_metrics: Callable[..., tuple] = classification_eval_metrics
    # ``predictions(logits) -> per-example distribution`` — the
    # inference export (softmax class probs for classifiers, next-token
    # distribution for LMs). Every registered model carries one, so the
    # serving tier (servesvc) builds its predict step from the registry
    # exactly the way the trainer builds its train step.
    predictions: Callable[[jax.Array], jax.Array] = classification_predictions
    # Sharded-execution support (long-context models only):
    # factory(seq_axis, model_axis, expert_axis=None) -> apply(params,
    # tokens_local, positions_local) -> logits_local, run inside
    # shard_map. Any axis may be None (unsharded); with seq_axis the
    # sequence dim is sharded (ring/all-to-all attention), with
    # model_axis params are tensor-parallel per ``tp_param_specs``,
    # with expert_axis MoE experts are sharded over it.
    sharded_apply_factory: (Callable[...,
                                     Callable[..., jax.Array]] | None) = None
    # factory(model_axis, expert_axis=None) -> params-shaped pytree of
    # PartitionSpec for tensor-/expert-parallel parameter placement.
    tp_param_specs: Callable[..., Any] | None = None
    # Pipeline-parallel support: pp_transform restacks init params into
    # the layer-stacked layout; pp_param_specs(stage_axis) are its
    # placement specs; pp_apply_factory(stage_axis, num_microbatches)
    # -> apply(params, tokens) -> logits inside shard_map.
    pp_transform: Callable[[Any], Any] | None = None
    pp_param_specs: Callable[[str], Any] | None = None
    pp_apply_factory: (Callable[[str, int], Callable[..., jax.Array]]
                       | None) = None
    # Interleaved-1F1B schedule support (mesh.pipeline_schedule="1f1b"):
    # pp_transform_chunked(params, S, v) restacks into the
    # chunk-interleaved layout; pp_1f1b_grads_factory(stage_axis, M, v,
    # model_axis=None, seq_axis=None, expert_axis=None) ->
    # grads_fn(params, tokens, labels) -> (loss, acc, grads) (the
    # fused forward/backward engine — no outer value_and_grad; under
    # seq_axis the outputs are per-shard partials the caller psums);
    # pp_1f1b_apply_factory(stage_axis, M, v, model_axis=None) ->
    # apply for eval.
    pp_transform_chunked: Callable[..., Any] | None = None
    pp_1f1b_grads_factory: Callable[..., Callable[..., tuple]] | None = None
    pp_1f1b_apply_factory: (Callable[..., Callable[..., jax.Array]]
                            | None) = None
    # Declarative parameter-placement rules (parallel/partition_rules):
    # partition_rules(axes: RuleAxes) -> ordered [(path-regex,
    # PartitionSpec)] list covering EVERY param leaf for whatever mix
    # of tp/pp/ep axes is active (inactive axes arrive as None and the
    # table leaves those dims unsharded). This is the single source the
    # spec engine maps over the real param tree — the per-shape
    # tp_param_specs/pp_param_specs builders above remain the models'
    # hand-built originals and the parity oracle for the tables.
    partition_rules: Callable[..., list] | None = None
    # Autoregressive-decode exports (causal LMs of the plain block, or
    # of a latent one with one residual stream, whatever its
    # feed-forwards but capacity routing; None elsewhere — the decode
    # service refuses models without them):
    # decode_prefill(params, tokens [b, s]) -> (logits [b, s, vocab],
    # k [L, b, s, h, hd], v [L, b, s, h, hd]) — the prompt forward
    # through the configured attention kernel that also exports every
    # layer's K/V for seeding a paged cache; decode_step(params,
    # tokens [S], positions [S], k_cache, v_cache [L, N, B, h, hd],
    # block_tables [S, P], lengths [S], block_size=B) -> (logits
    # [S, vocab], k_cache, v_cache) — one incremental token over the
    # paged cache, a single compiled shape for any mix of sequence
    # lengths. decode_cache_shape = (num_layers, num_heads, head_dim),
    # the geometry the cache is allocated with, handed to
    # ``PagedKVCache`` as it is: a latent model's is (num_layers, 1,
    # (kv_latent, qk_rope)), one row a token for all heads, the latent in
    # ``k`` and the rotated key in ``v``. A model with per-token routed
    # layers takes ``return_routing=True`` on both exports, which adds
    # the chosen expert ids as a last output ([routed_layers, b, s, k];
    # [routed_layers, S, k]) and changes nothing else; where
    # ``decode_counts`` is set, ``decode_step(..., return_counts=True)``
    # adds, last, the pairs each held expert took of this step's tokens,
    # int32 [routed_layers, held].
    decode_prefill: Callable[..., tuple] | None = None
    decode_step: Callable[..., tuple] | None = None
    decode_cache_shape: tuple | None = None
    decode_counts: bool = False
    # A model with state-space layers keeps, beside the paged rows of its
    # attention layers (``decode_cache_shape`` counts those layers and
    # their key-value heads), arrays a SEQUENCE owns whole:
    # decode_state_shape = (mixer layers, state size N, channels E,
    # convolution taps before the newest K - 1[, the tail's width where it
    # is not E]), what ``servesvc.kv_cache.SlotState`` is allocated with:
    # a state-space layer's (layers, N, E, K - 1), a delta-rule layer's
    # (layers, (heads, D), D, K - 1, 3 x heads x D). Its exports take
    # them: decode_prefill(params, tokens [b, s], lengths [b]) ->
    # (logits [b, 1, vocab] of position lengths - 1, k, v, state [Lm, b,
    # N, E], tail [Lm, K - 1, b, E]);
    # decode_step(params, tokens, positions, k_cache, v_cache,
    # block_tables, lengths, state, tail, block_size=B) -> (logits,
    # k_cache, v_cache, state, tail), ``state`` and ``tail`` an array a
    # mixer layer, [S, N, E] and [K - 1, S, width]; both take
    # ``return_routing`` (and the step ``return_counts``) as the paged
    # exports do, the routing and the counts last. Such a model's record
    # is a :class:`SessionModel`.
    decode_state_shape: tuple | None = None
    # When True, ``apply`` and the sharded applies accept
    # ``return_aux=True`` and return (logits, aux), ``aux`` a mapping:
    # the train step adds ``aux_weight * aux["loss"]`` (the load-balance
    # loss of capacity-routed experts; with ``train=True`` the
    # next-next-token module's term) and logs ``aux["counts"]`` where a
    # per-token routed layer gives it; ``aux["routing"]`` is the expert
    # ids such layers chose.
    has_aux: bool = False
    aux_weight: float = 0.0
    # True when ``apply(train=True)`` consumes ``dropout_key``. The
    # SP/PP loss paths do not thread a dropout key (parallel/api.py);
    # they refuse such a model rather than silently training without
    # dropout.
    uses_dropout: bool = False


@dataclasses.dataclass(frozen=True)
class SessionModel(Model):
    """A model whose decode state is not two paged arrays alone, so that
    no one can drive a sequence through it by the paged exports and a
    scratch cache: ``decode_session(params, dcfg, cache_dtype)`` is one
    sequence through the decode replica's own prefill, step and stores
    (``servesvc.decode.SlotSession``; the serving check's contract is in
    benchmark/lib/cell.py). A record without the attribute is driven
    through its paged exports."""
    decode_session: Callable[..., Any] | None = None


# ---------------------------------------------------------------------------
# Default partition-rule tables (the per-model regex→PartitionSpec
# tables the spec engine maps over real param trees; see
# parallel/partition_rules.match_partition_rules)
# ---------------------------------------------------------------------------

def replicated_partition_rules(axes) -> list:
    """Every leaf replicated — the table for models with no
    tensor/pipeline/expert parallelism support (cnn, resnet)."""
    del axes
    return [(r".*", PartitionSpec())]


def transformer_partition_rules(num_experts: int):
    """The transformer's table, parameterized like its hand-built spec
    functions: Megatron column/row TP on the model axis, experts on the
    expert axis, and — when ``axes.stage`` is set — the stacked
    (pipeline) layout whose block leaves carry a leading layer dim
    sharded over the stage axis. Flat-layout block paths look like
    ``blocks/3/wqkv``; stacked ones like ``blocks/wqkv`` — distinct
    regexes, so one call's table is unambiguous either way."""
    def rules(axes) -> list:
        P = PartitionSpec
        m, e, s = axes.model, axes.expert, axes.stage
        out: list = []
        if s is not None:
            # stacked layout: leading layer dim over the stage axis
            out += [
                (r"blocks/wqkv$", P(s, None, None, m)),
                (r"blocks/wo$", P(s, m, None)),
                (r"blocks/(ln1|ln2)/scale$", P(s)),
            ]
            if num_experts > 0:
                out += [(r"blocks/router$", P(s)),
                        (r"blocks/w1$", P(s, e, None, m)),
                        (r"blocks/w2$", P(s, e, m, None))]
            else:
                out += [(r"blocks/w1$", P(s, None, m)),
                        (r"blocks/w2$", P(s, m, None))]
        else:
            out += [
                (r"blocks/\d+/wqkv$", P(None, None, m)),
                (r"blocks/\d+/wo$", P(m, None)),
            ]
            if num_experts > 0:
                out += [(r"blocks/\d+/router$", P()),
                        (r"blocks/\d+/w1$", P(e, None, m)),
                        (r"blocks/\d+/w2$", P(e, m, None))]
            else:
                out += [(r"blocks/\d+/w1$", P(None, m)),
                        (r"blocks/\d+/w2$", P(m, None))]
        # embeddings and norms replicated in every layout (stacked block
        # norms matched above first — first match wins)
        out += [(r"(^|/)(ln1|ln2|final_norm)/scale$", P()),
                (r"^(embed|pos)$", P())]
        return out
    return rules


_REGISTRY: dict[str, Callable[[ModelConfig], Model]] = {}


def register(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


def available() -> list[str]:
    return sorted(_REGISTRY)


def get_model(cfg: ModelConfig) -> Model:
    if cfg.name not in _REGISTRY:
        raise ValueError(f"unknown model {cfg.name!r}; available: {available()}")
    return _REGISTRY[cfg.name](cfg)


@register("mnist_cnn")
def _mnist_cnn(cfg: ModelConfig) -> Model:
    from . import cnn
    compute_dtype = jnp.dtype(cfg.compute_dtype)

    def init(key):
        return cnn.init(key, image_size=cfg.image_size,
                        num_channels=cfg.num_channels,
                        num_classes=cfg.num_classes)

    def apply(params, x, *, train=False, dropout_key=None):
        return cnn.apply(params, x, train=train, dropout_key=dropout_key,
                         dropout_rate=cfg.dropout_rate,
                         compute_dtype=compute_dtype)

    return Model(name=cfg.name, init=init, apply=apply,
                 loss=cnn.loss_fn, accuracy=cnn.accuracy,
                 input_shape=(cfg.image_size, cfg.image_size, cfg.num_channels),
                 partition_rules=replicated_partition_rules,
                 predictions=cnn.predictions,  # the reference's export
                 uses_dropout=cfg.dropout_rate > 0.0)


@register("resnet20")
def _resnet20(cfg: ModelConfig) -> Model:
    from . import resnet
    compute_dtype = jnp.dtype(cfg.compute_dtype)

    def init(key):
        return resnet.init(key, num_classes=cfg.num_classes,
                           num_channels=cfg.num_channels)

    def apply(params, x, *, train=False, dropout_key=None):
        del dropout_key  # resnet20 has no dropout
        return resnet.apply(params, x, train=train, compute_dtype=compute_dtype)

    from . import cnn
    return Model(name=cfg.name, init=init, apply=apply,
                 loss=cnn.loss_fn, accuracy=cnn.accuracy,
                 input_shape=(cfg.image_size, cfg.image_size, cfg.num_channels),
                 partition_rules=replicated_partition_rules)


@register("transformer")
def _transformer(cfg: ModelConfig) -> Model:
    from . import transformer
    compute_dtype = jnp.dtype(cfg.compute_dtype)

    moe = cfg.num_experts > 0
    # the sizes beyond the plain block, each absent at its default
    latent = cfg.kv_latent_dim > 0
    routed = cfg.routed_experts > 0
    held = (cfg.first_held_expert, cfg.held_experts or cfg.routed_experts)
    sizes = None
    mixed = cfg.ssm_state_dim > 0 or cfg.kda_head_dim > 0
    if (latent or routed or cfg.ffn_dim or cfg.residual_streams > 1
            or cfg.nextn_layers or cfg.sandwich_norm or cfg.kv_heads
            or mixed or cfg.attn_head_gate):
        if cfg.ssm_state_dim and cfg.kda_head_dim:
            raise ValueError("model.ssm_state_dim and model.kda_head_dim "
                             "name two mixers for one layer")
        if cfg.kda_head_dim and (
                moe or cfg.residual_streams > 1 or cfg.nextn_layers
                or cfg.sandwich_norm):
            raise ValueError(
                "delta-rule layers (model.kda_head_dim) are built with one "
                "residual stream, no norm on a sublayer's output, no "
                "next-next-token module and no capacity routing")
        if (cfg.ssm_state_dim or cfg.kv_heads) and (
                moe or latent or routed or cfg.residual_streams > 1
                or cfg.nextn_layers or cfg.sandwich_norm):
            raise ValueError(
                "state-space layers (model.ssm_state_dim) and grouped "
                "key-value heads (model.kv_heads) are built with the "
                "dense or gated feed-forward, the plain residual and "
                "attention through wqkv (delta-rule layers, "
                "model.kda_head_dim, also beside latent attention and "
                "per-token routing)")
        if cfg.attn_head_gate and not latent:
            raise ValueError("model.attn_head_gate gates a latent "
                             "attention layer's heads (kv_latent_dim > 0)")
        if moe and routed:
            raise ValueError("model.num_experts (capacity routing) and "
                             "model.routed_experts (per-token routing) "
                             "name two feed-forwards for one block")
        if routed and not (0 <= held[0] and held[0] + held[1]
                           <= cfg.routed_experts
                           and 0 < cfg.experts_per_token
                           <= cfg.routed_experts and cfg.expert_ffn_dim > 0):
            raise ValueError(
                f"held experts {held} of {cfg.routed_experts}, "
                f"{cfg.experts_per_token} a token, {cfg.expert_ffn_dim} "
                "wide: not a share of a routed layer")
        groups = (cfg.router_groups, cfg.router_topk_groups)
        if routed and groups != (1, 1) and not (
                0 < groups[1] <= groups[0]
                and cfg.routed_experts % groups[0] == 0
                and cfg.routed_experts // groups[0] >= 2
                and cfg.experts_per_token
                <= groups[1] * (cfg.routed_experts // groups[0])):
            raise ValueError(
                f"{groups[1]} of {groups[0]} groups over "
                f"{cfg.routed_experts} experts, {cfg.experts_per_token} a "
                "token: not a group limit (equal groups of at least two "
                "experts, enough experts in the groups kept)")
        sizes = transformer.Sizes(
            q_latent_dim=cfg.q_latent_dim, kv_latent_dim=cfg.kv_latent_dim,
            qk_nope_dim=cfg.qk_nope_dim, qk_rope_dim=cfg.qk_rope_dim,
            v_head_dim=cfg.v_head_dim, ffn_dim=cfg.ffn_dim,
            routed_experts=cfg.routed_experts, held=held,
            shared_experts=cfg.shared_experts,
            expert_ffn_dim=cfg.expert_ffn_dim, dense_layers=cfg.dense_layers,
            residual_streams=cfg.residual_streams,
            nextn_layers=cfg.nextn_layers, sandwich_norm=cfg.sandwich_norm,
            kv_heads=cfg.kv_heads, ssm_state_dim=cfg.ssm_state_dim,
            ssm_expand=cfg.ssm_expand, ssm_conv=cfg.ssm_conv,
            ssm_dt_rank=cfg.ssm_dt_rank,
            attn_period=cfg.attn_layer_period,
            attn_offset=cfg.attn_layer_offset,
            kda_head_dim=cfg.kda_head_dim, kda_conv=cfg.kda_conv,
            kda_lower_bound=cfg.kda_lower_bound,
            attn_head_gate=cfg.attn_head_gate,
            # a bias that never moves is no bias: a served model without
            # one in its source keeps the leaf at zeros
            **({} if cfg.router_bias_rate else {"router_bias_init": 0.0}))
    # what the train step adds of aux["loss"]: the load-balance loss at
    # its weight; the next-next-token module's term carries its own
    aux_weight = cfg.moe_aux_weight if moe else 1.0

    def init(key):
        return transformer.init(
            key, vocab_size=cfg.vocab_size, model_dim=cfg.model_dim,
            num_heads=cfg.num_heads, num_layers=cfg.num_layers,
            max_seq_len=cfg.seq_len, num_experts=cfg.num_experts,
            sizes=sizes)

    if cfg.attention_impl == "flash":
        from ..ops.pallas_attention import (flash_attention,
                                            flash_attention_bshd)
        # the model body sees the bshd entry (no head transposes); the
        # SP wrappers below keep the bhsd entry — Ulysses' all-to-all
        # output is already head-major
        attention_fn = flash_attention_bshd
        inner_bhsd = flash_attention
    elif cfg.attention_impl == "dense":
        attention_fn = None  # transformer defaults to local_self_attention
        inner_bhsd = None
    else:
        raise ValueError(f"unknown attention_impl {cfg.attention_impl!r}")

    def make_seq_attn(seq_axis: str | None):
        """The attention callable for a given seq sharding: the plain
        configured kernel when unsharded, else ring / Ulysses over the
        axis (shared by the SP/TP path and the pipeline path)."""
        if seq_axis is None:
            return attention_fn  # flash or dense, per attention_impl
        if cfg.sp_attention == "ring":
            from ..ops.ring_attention import ring_self_attention

            def sharded_attn(q, k, v, causal=True, scale=None):
                return ring_self_attention(q, k, v, seq_axis, causal=causal,
                                           scale=scale)
            return sharded_attn
        if cfg.sp_attention == "ulysses":
            from ..ops.ulysses_attention import ulysses_self_attention
            inner = inner_bhsd

            def sharded_attn(q, k, v, causal=True, scale=None):
                return ulysses_self_attention(q, k, v, seq_axis,
                                              causal=causal, scale=scale,
                                              attention_fn=inner)
            return sharded_attn
        raise ValueError(f"unknown sp_attention {cfg.sp_attention!r}")

    def block_for(seq_axis: str | None = None, model_axis: str | None = None,
                  expert_axis: str | None = None, *,
                  pipeline: str | None = None,
                  layer: int = 0) -> transformer.Block:
        """The configured layer ``layer`` under the given mesh axes (any
        may be None: unsharded) — the one reader of the configuration's
        attention, feed-forward and residual choices, and the one place
        their combinations are refused. ``pipeline``: the schedule
        (``"gpipe"`` / ``"1f1b"``) whose train step the block is for."""
        ring = seq_axis is not None and cfg.sp_attention == "ring"
        if sizes is not None and (seq_axis or model_axis or expert_axis
                                  or pipeline):
            raise NotImplementedError(
                "latent attention, per-token routing (and its group "
                "limit), the gated unit, residual streams, the "
                "next-next-token module, sandwich norms, grouped "
                "key-value heads, a head-wise output gate and mixer "
                "layers (state-space, delta-rule) run unsharded or "
                "data-parallel; no partition rule, sharded apply or "
                "pipeline stage is written for them")
        if expert_axis is not None and not moe:
            raise ValueError("mesh has expert parallelism but the model "
                             "has no experts (model.num_experts == 0)")
        if pipeline and cfg.remat and cfg.remat_policy != "full":
            # a silently-ignored policy would leave the user at
            # full-remat throughput while believing save_attn is on
            how = ("the 1f1b schedule (chunk recompute is built into the "
                   "engine)" if pipeline == "1f1b" else
                   "pipeline parallelism (stage scans use full per-layer "
                   "remat)")
            raise ValueError(
                f"model.remat_policy={cfg.remat_policy!r} is not "
                f"supported under {how}; set remat_policy='full'")
        if cfg.remat and cfg.remat_policy == "save_attn":
            if cfg.attention_impl != "flash":
                # save_attn keeps the attention sublayer's AD residuals
                # resident; only the flash kernel's custom VJP bounds
                # those at O(s·d) — dense attention would park the
                # [b, h, s, s] softmax probabilities in HBM per layer,
                # defeating remat entirely
                raise ValueError(
                    "model.remat_policy='save_attn' requires "
                    "attention_impl='flash' (dense attention has no fused "
                    "VJP; its resident residuals would be O(seq²) per "
                    "layer)")
            if ring:
                # ring attention has no custom vjp — AD would save its
                # per-ppermute-step scan residuals instead, exactly the
                # memory remat exists to avoid
                raise ValueError(
                    "model.remat_policy='save_attn' requires an attention "
                    "with a fused VJP (flash / Ulysses-over-flash); ring "
                    "attention under sequence parallelism needs "
                    "remat_policy='full'")
        if pipeline == "1f1b" and ring:
            raise ValueError(
                "pipeline_schedule='1f1b' with sequence parallelism "
                "requires model.sp_attention='ulysses': ring attention's "
                "ppermute rendezvouses globally and deadlocks inside the "
                "fused engine's stage-varying branches (all_to_all is "
                "group-local and composes; use 'gpipe' for ring)")
        feed_forward = None  # the dense ReLU product
        if moe:
            # SP×MoE: tokens are already seq-sharded; routing runs on
            # each shard's slice with shard-local capacity (ops/moe.py
            # module doc), while the aux statistics average over the seq
            # axis so the load-balance loss stays the exact full-token
            # value. Under a pipeline each tick's calls see one
            # microbatch's slice of one seq shard, and the tick
            # accumulation completes the same average.
            feed_forward = transformer.moe_feed_forward(
                num_experts=cfg.num_experts,
                capacity_factor=cfg.expert_capacity_factor,
                router_top_k=cfg.moe_router_top_k,
                num_groups=cfg.moe_num_groups,
                expert_axis=expert_axis, tp_axis=model_axis,
                stats_axes=() if seq_axis is None else (seq_axis,))
        projections, residual = None, transformer.PLAIN
        if routed and layer >= cfg.dense_layers:
            feed_forward = transformer.moe_feed_forward(
                total=cfg.routed_experts, held=held,
                top_k=cfg.experts_per_token, scaling=cfg.routed_scaling,
                bias_rate=cfg.router_bias_rate,
                n_group=cfg.router_groups,
                topk_group=cfg.router_topk_groups)
        elif cfg.ffn_dim:
            feed_forward = transformer.gated_feed_forward
        if latent:
            projections = transformer.latent_projections(
                num_heads=cfg.num_heads, qk_nope_dim=cfg.qk_nope_dim,
                qk_rope_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
                rope_theta=cfg.rope_theta, rope_factor=cfg.rope_factor,
                rope_original_len=cfg.rope_original_len or cfg.seq_len,
                rope_beta_fast=cfg.rope_beta_fast,
                rope_beta_slow=cfg.rope_beta_slow,
                rope_mscale=cfg.rope_mscale,
                rope_mscale_all_dim=cfg.rope_mscale_all_dim,
                norm_eps=cfg.norm_eps)
        if cfg.sandwich_norm or (cfg.kda_head_dim and routed):
            # (a delta-rule model that routes: twelve routers of 512
            # read what eleven matrix-valued states wrote, and each
            # rounding on a router's way costs it near ties)
            residual = transformer.FLOAT32
        if cfg.residual_streams > 1:
            residual = transformer.stream_residual(
                cfg.residual_streams, iters=cfg.sinkhorn_iters,
                eps=cfg.residual_eps, clamp=cfg.residual_clamp)
        return transformer.make_block(
            num_heads=cfg.num_heads, attention_fn=make_seq_attn(seq_axis),
            model_axis=model_axis, feed_forward=feed_forward,
            projections=projections, residual=residual,
            out_norm=cfg.sandwich_norm, norm_eps=cfg.norm_eps,
            kv_heads=cfg.kv_heads,
            mixer=(sizes is not None and not sizes.attends(layer)
                   and sizes.mixer),
            kda_lower_bound=cfg.kda_lower_bound)

    # one device, or replicas of the whole model. A layer's kind is a
    # pair: whether it attends (the pattern of the model section: a mixer
    # elsewhere) and whether its feed-forward is routed (the leading
    # ``dense_layers`` are not). Where the layers are of more than one
    # kind, a block a layer, one built a kind; the last also the
    # next-next-token module's
    kinds = [(sizes is None or sizes.attends(i),
              routed and i >= cfg.dense_layers)
             for i in range(cfg.num_layers)]
    block = block_for()
    if len(set(kinds)) > 1:
        per_kind = {kind: block_for(layer=kinds.index(kind))
                    for kind in set(kinds)}
        block = tuple(per_kind[kind] for kind in kinds)

    def apply(params, x, *, train=False, dropout_key=None, return_aux=False):
        del dropout_key
        return transformer.apply(params, x, block=block,
                                 compute_dtype=compute_dtype,
                                 remat=cfg.remat,
                                 remat_policy=cfg.remat_policy,
                                 return_aux=return_aux, train=train,
                                 nextn_loss_weight=cfg.nextn_loss_weight)

    def sharded_apply_factory(seq_axis: str | None, model_axis: str | None,
                              expert_axis: str | None = None):
        """Sharded apply for the DP×SP×TP×EP train step: tokens arrive
        as [b, seq_local] slices; attention crosses seq shards via the
        configured strategy; params may be tensor-parallel and/or
        expert-parallel shards."""
        sharded = block_for(seq_axis, model_axis, expert_axis)

        def apply_sharded(params, tokens, positions, return_aux=False):
            return transformer.apply(params, tokens, block=sharded,
                                     positions=positions,
                                     compute_dtype=compute_dtype,
                                     remat=cfg.remat,
                                     remat_policy=cfg.remat_policy,
                                     return_aux=return_aux)

        return apply_sharded

    def pp_apply_factory(stage_axis: str, num_microbatches: int,
                         model_axis: str | None = None,
                         seq_axis: str | None = None,
                         expert_axis: str | None = None):
        staged = block_for(seq_axis, model_axis, expert_axis,
                           pipeline="gpipe")

        def apply_pp(params, tokens, positions=None, return_aux=False):
            return transformer.apply_pp(
                params, tokens, block=staged, stage_axis=stage_axis,
                num_microbatches=num_microbatches, positions=positions,
                compute_dtype=compute_dtype, remat=cfg.remat,
                return_aux=return_aux)
        return apply_pp

    def pp_1f1b_grads_factory(stage_axis: str, num_microbatches: int,
                              num_chunks: int,
                              model_axis: str | None = None,
                              seq_axis: str | None = None,
                              expert_axis: str | None = None):
        staged = block_for(seq_axis, model_axis, expert_axis,
                           pipeline="1f1b")

        def grads_fn(params, tokens, labels):
            return transformer.grads_pp_1f1b(
                params, tokens, labels, block=staged,
                stage_axis=stage_axis, num_microbatches=num_microbatches,
                num_chunks=num_chunks, seq_axis=seq_axis,
                aux_weight=aux_weight, compute_dtype=compute_dtype)
        return grads_fn

    def pp_1f1b_apply_factory(stage_axis: str, num_microbatches: int,
                              num_chunks: int,
                              model_axis: str | None = None,
                              expert_axis: str | None = None):
        staged = block_for(None, model_axis, expert_axis)

        def apply_1f1b(params, tokens):
            return transformer.apply_pp_1f1b(
                params, tokens, block=staged, stage_axis=stage_axis,
                num_microbatches=num_microbatches, num_chunks=num_chunks,
                compute_dtype=compute_dtype)
        return apply_1f1b

    # Decode exports: the plain block, and a latent one with one residual
    # stream (its dense, gated and per-token routed feed-forwards run a
    # token at a time as they run a batch). Capacity routing
    # (num_experts) is computed over groups of a sequence's tokens, which
    # one incremental token does not have; several residual streams have
    # no cache-side forward (transformer._one_stream); and the plain
    # step's own attention (transformer._decode_attn) spells out the
    # plain block: no output norm, no other epsilon, no gated tree
    decode_prefill = decode_step_fn = decode_cache_shape = None
    decode_state_shape = None

    def asked_of(out, stores: int, return_routing, return_counts):
        """A step's outputs (the logits and its stores, ``stores`` values,
        then where anything was asked the routed layers' ``aux``) with the
        routing and the counts in ``aux``'s place, each where asked."""
        if not (return_routing or return_counts):
            return out
        aux = out[stores]
        return (*out[:stores],
                *((aux["routing"],) if return_routing else ()),
                *((aux["counts"],) if return_counts else ()))

    blocks = ((block,) * cfg.num_layers
              if isinstance(block, transformer.Block) else block)
    first = blocks[0]
    if mixed:
        # a layer's state is a sequence's: rows a token for the layers
        # that attend, one state and one convolution tail a slot for the
        # others, through forwards that hand both over
        kv_heads = cfg.kv_heads or cfg.num_heads

        def decode_prefill(params, tokens, lengths, return_routing=False):
            return transformer.prefill_with_state(
                params, tokens, lengths, block=blocks,
                compute_dtype=compute_dtype, return_routing=return_routing)

        def decode_step_fn(params, tokens, positions, k_cache, v_cache,
                           block_tables, lengths, state, tail, *,
                           block_size, attention_kernel="auto",
                           return_routing=False, return_counts=False):
            out = transformer.decode_step_with_state(
                params, tokens, positions, k_cache, v_cache, block_tables,
                lengths, state, tail, block=blocks, num_heads=cfg.num_heads,
                kv_heads=kv_heads, block_size=block_size,
                compute_dtype=compute_dtype,
                attention_kernel=attention_kernel,
                return_aux=return_routing or return_counts)
            return asked_of(out, 5, return_routing, return_counts)

        attending = sum(sizes.attends(i) for i in range(cfg.num_layers))
        decode_cache_shape = (
            (attending, 1, (cfg.kv_latent_dim, cfg.qk_rope_dim)) if latent
            else (attending, kv_heads, cfg.model_dim // cfg.num_heads))
        if cfg.kda_head_dim:
            # a head's matrix a sequence, the heads apart; the tail as
            # wide as the three convolved streams
            decode_state_shape = (
                cfg.num_layers - attending,
                (cfg.num_heads, cfg.kda_head_dim), cfg.kda_head_dim,
                cfg.kda_conv - 1, 3 * cfg.num_heads * cfg.kda_head_dim)
        else:
            decode_state_shape = (cfg.num_layers - attending,
                                  cfg.ssm_state_dim,
                                  cfg.ssm_expand * cfg.model_dim,
                                  cfg.ssm_conv - 1)
    # (grouped heads are served beside state-space layers only: the plain
    # step's attention reads `wqkv` as three equal parts)
    elif not moe and not cfg.kv_heads and (
            sizes is None or (first.decode_attn is not None
                              and cfg.residual_streams == 1)):
        def decode_prefill(params, tokens, positions=None,
                           return_routing=False):
            return transformer.prefill_with_kv(
                params, tokens, block=block, positions=positions,
                compute_dtype=compute_dtype,
                return_routing=return_routing)

        def decode_step_fn(params, tokens, positions, k_cache, v_cache,
                           block_tables, lengths, *, block_size,
                           attention_kernel="auto", return_routing=False,
                           return_counts=False):
            out = transformer.decode_step(
                params, tokens, positions, k_cache, v_cache,
                block_tables, lengths,
                ffn=tuple(bk.ffn for bk in blocks),
                attn=first.decode_attn, norm=first.norm,
                residual=first.residual, num_heads=cfg.num_heads,
                block_size=block_size, compute_dtype=compute_dtype,
                attention_kernel=attention_kernel,
                return_aux=return_routing or return_counts)
            return asked_of(out, 3, return_routing, return_counts)

        decode_cache_shape = (
            (cfg.num_layers, 1, (cfg.kv_latent_dim, cfg.qk_rope_dim))
            if latent else
            (cfg.num_layers, cfg.num_heads, cfg.model_dim // cfg.num_heads))

    model = Model(name=cfg.name, init=init, apply=apply,
                 loss=transformer.loss_fn, accuracy=transformer.accuracy,
                 input_shape=(cfg.seq_len,), input_dtype=jnp.int32,
                 eval_metrics=lm_eval_metrics,
                 predictions=lm_predictions,
                 decode_prefill=decode_prefill,
                 decode_step=decode_step_fn,
                 decode_cache_shape=decode_cache_shape,
                 decode_state_shape=decode_state_shape,
                 decode_counts=routed and decode_step_fn is not None,
                 sharded_apply_factory=sharded_apply_factory,
                 partition_rules=(replicated_partition_rules
                                  if sizes is not None else
                                  transformer_partition_rules(
                                      cfg.num_experts)),
                 has_aux=moe or routed or cfg.nextn_layers > 0,
                 aux_weight=aux_weight,
                 tp_param_specs=lambda axis, expert_axis=None:
                     transformer.param_partition_specs(
                         cfg.num_layers, axis, cfg.num_experts, expert_axis),
                 pp_transform=transformer.stack_block_params,
                 pp_param_specs=lambda stage_axis, model_axis=None,
                 expert_axis=None: transformer.pp_param_partition_specs(
                     stage_axis, model_axis, cfg.num_experts, expert_axis),
                 pp_apply_factory=pp_apply_factory,
                 pp_transform_chunked=transformer.stack_block_params_chunked,
                 pp_1f1b_grads_factory=pp_1f1b_grads_factory,
                 pp_1f1b_apply_factory=pp_1f1b_apply_factory)
    if decode_state_shape is None:
        return model

    def decode_session(params, dcfg, cache_dtype):
        from ..servesvc.decode import SlotSession
        return SlotSession(model, params, dcfg, cache_dtype)
    return SessionModel(**{f.name: getattr(model, f.name)
                           for f in dataclasses.fields(model)},
                        decode_session=decode_session)
