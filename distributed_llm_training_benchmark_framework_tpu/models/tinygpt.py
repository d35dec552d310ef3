"""TinyGPT — decoder-style benchmark transformer, pure functional JAX.

Capability parity with the reference model (reference
``benchmarking/train_harness.py:36-131``, classes ``TinyGPT`` /
``TransformerBlock``): token embedding + learned positional embedding +
embedding dropout + N pre-LN blocks (multi-head attention + 4x GELU MLP, both
with residuals) + final LayerNorm + weight-tied LM head + cross-entropy loss
with ``ignore_index=-1``.

TPU-first design differences (deliberate, not omissions):

- **Functional, pytree params.** No module objects. Parameters are a nested
  dict of arrays so every leaf can carry a ``jax.sharding.NamedSharding`` —
  strategies are data (PartitionSpecs), not wrapper classes.
- **Stacked layers + ``lax.scan``.** All N blocks' weights are stacked on a
  leading ``layers`` axis and the forward scans over them. One trace/compile of
  the block regardless of depth — compile time stays flat from tier S to
  tier B, and ``jax.checkpoint`` (remat) applies uniformly per-layer.
- **Mixed precision the TPU way.** Params live in fp32; matmuls run in
  bfloat16 on the MXU with fp32 accumulation (``preferred_element_type``);
  LayerNorm, softmax and the loss stay fp32. (The reference runs fp16 AMP for
  DDP/FSDP and bf16 for ZeRO — reference ``train_harness.py:334-335`` vs
  ``configs/deepspeed/zero2.json:7-9``; on TPU bf16 is the native fast path.)
- **Attention is maskless by default** for benchmark parity: the reference
  passes no causal mask (reference ``train_harness.py:127``), so it benchmarks
  bidirectional attention compute. ``causal=True`` is available as a real
  option, as is a Pallas flash-attention kernel (``ops.flash_attention``).

Tier table matches reference ``get_model_config`` (``train_harness.py:157-179``):
tier A = 1024d/16h/16L (~236M params with tied embeddings), tier B =
2048d/32h/32L (~1.68B). Tier S is ours, for CPU tests/smoke runs.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..utils import scopes
from . import mixers
from .common import MLP_GU, Params, _dropout, _norm
from .common import normal as _normal
from .mixers.attention import qk_prologue_tables

# For ``perfbench/`` alone, which reads these from here (``tinygpt.kda_stats``, ...): the
# next ``benchmark`` PR can read each from its home in ``models/mixers/`` and drop this block.
from .mixers.attention import attn_mask_stats, bd_mask_stats, qk_prologue_stats  # noqa: F401
from .mixers.attention import sublayer as _attention_sublayer  # noqa: F401
from .mixers.conv import sconv_stats  # noqa: F401
from .mixers.kda import kda_stats  # noqa: F401
from .mixers.ssd import ssd_stats  # noqa: F401

REMAT_POLICIES = ("none", "dots", "full_keep_kernels", "full")

#: ``checkpoint_name``s of matmul results in their compute-dtype form, which
#: ``full_keep_kernels`` keeps (``_under_remat``): the mixers' wide products
#: (each module's ``CAST_NAMES``: a KDA layer's q, k, v projection, an SSD
#: layer's x | B | C and z, a ``conv`` layer's B | C | x~), a dense SwiGLU
#: layer's gate+up (``MLP_GU``, ``_mlp_sublayer``) and a shared expert's up
#: product where it is not gated (``common.SHARED_U``, ``moe._shared_experts``).
MATMUL_CAST_NAMES = mixers.MATMUL_CAST_NAMES


def normalize_remat(value: Any) -> str:
    """Normalize a remat policy: accepts one of ``REMAT_POLICIES`` or a legacy
    bool (True = "full"). "auto" must be resolved (utils.memory
    .resolve_auto_remat) before it reaches the model."""
    if isinstance(value, bool):
        return "full" if value else "none"
    if value in REMAT_POLICIES:
        return value
    raise ValueError(
        f"invalid remat policy {value!r} (expected one of {REMAT_POLICIES}, "
        "a bool, or 'auto' resolved upstream)"
    )


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN's rotary frequencies (arXiv:2309.00071) as DeepSeek-V2 uses them:
    the config's ``rope_scaling`` group. Dimensions that turn more than
    ``beta_fast`` times over the original context keep their frequency, those
    that turn fewer than ``beta_slow`` times have it divided by ``factor``,
    and a linear ramp blends the ones between."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def correction_range(self, dim: int, theta: float) -> Tuple[int, int]:
        """(low, high): the ramp's first and last frequency index."""
        def turns_at(n):  # the (fractional) index whose frequency turns n times
            return dim * math.log(
                self.original_max_position_embeddings / (n * 2 * math.pi)
            ) / (2 * math.log(theta))

        return (max(math.floor(turns_at(self.beta_fast)), 0),
                min(math.ceil(turns_at(self.beta_slow)), dim - 1))

    def inv_freq(self, dim: int, theta: float) -> np.ndarray:
        """(dim / 2,) float32 rotary frequencies."""
        i = np.arange(dim // 2, dtype=np.float64)
        plain = theta ** (-2.0 * i / dim)
        low, high = self.correction_range(dim, theta)
        ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
        return (plain / self.factor * ramp + plain * (1.0 - ramp)).astype(np.float32)

    @staticmethod
    def _mscale(factor: float, mscale: float) -> float:
        return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0

    @property
    def cos_sin_factor(self) -> float:
        """What multiplies cos and sin: 1 where the two mscales agree."""
        return (self._mscale(self.factor, self.mscale)
                / self._mscale(self.factor, self.mscale_all_dim))

    @property
    def softmax_factor(self) -> float:
        """What multiplies 1 / sqrt(width of q) in the softmax: m^2."""
        return self._mscale(self.factor, self.mscale_all_dim) ** 2


#: The kinds of layer a stack can mix (``TinyGPTConfig.layer_types``); a kind
#: chooses the layer's mixer, a module of ``models/mixers/`` (``mixers.MIXERS``):
#: ``global`` is softmax attention over every earlier position, ``window`` over
#: the last ``sliding_window``, ``kda`` the gated delta rule, ``ssd`` a Mamba-2
#: mixer, ``conv`` a gated short convolution. Also the names of their scopes
#: under ``attention``. ``mlp`` is no mixer: under ``block_halves`` a block of
#: that kind is the feed-forward part alone, a mixer's kind the mixer alone.
#: Layers of one stack of the parameter tree have equal leaves (``layer_groups``: by the
#: mixer, ``mixers.STACKS``, a leading dense MLP and, under ``layer_heads``, the kind).
LAYER_KINDS = (scopes.GLOBAL, scopes.WINDOW, scopes.KDA, scopes.SSD, scopes.MLP, scopes.CONV)



@dataclasses.dataclass(frozen=True)
class Rotary:
    """One kind of layer's rotary table: plain ``theta``, or YaRN's
    frequencies over it with cos and sin times ``scaling.cos_sin_factor``
    (the Hugging Face ``attention_factor``; the softmax scale is untouched).
    ``rotary_dim``: the leading lanes of a head that rotate (``partial_rotary_
    factor`` x head_dim, rotate-half inside them: lane j with j + rotary_dim /
    2; the frequencies are over ``rotary_dim``), the rest pass unrotated. None:
    the whole head."""

    theta: float
    scaling: Optional[YarnScaling] = None
    rotary_dim: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class BlockDiffusionObjective:
    """Block-diffusion training (BD3-LM, arXiv:2503.09573; the SDAR recipe): a
    document of L tokens is cut into blocks of ``block`` tokens, each block b
    draws t_b ~ U[t_min, t_max] and each of its tokens is replaced by
    ``mask_id`` with probability t_b (the linear schedule). The model runs
    once over the stream [noisy copy ; clean copy] of 2L tokens, both copies at
    positions 0..L-1, under ``ops.flash_attention.BlockDiffusion``'s mask, and
    the loss is (1/L) sum over masked i of CE(logits_noisy[i], x[i]) / t_blk(i):
    a masked position predicts its own token (no shift), and only the noisy
    copy goes through the head."""

    block: int
    mask_id: int
    t_min: float = 1e-3
    t_max: float = 1.0


@dataclasses.dataclass(frozen=True)
class TinyGPTConfig:
    vocab_size: int = 32000
    n_embd: int = 768
    n_head: int = 12
    n_layer: int = 12
    block_size: int = 4096
    dropout: float = 0.1
    # Parity default: the reference applies no causal mask (train_harness.py:127).
    causal: bool = False
    # 'reference' = jnp softmax attention; 'flash' = Pallas TPU kernel;
    # 'ring' = ring attention over a sequence-parallel mesh axis.
    attention_impl: str = "reference"
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    # Per-layer rematerialization policy (`_under_remat` has the rule): "none"
    # saves every intermediate; "dots" keeps matmul outputs and the values
    # `remat_kept_names` lists (the kernels' results, the router's); only cheap
    # elementwise/norm work is recomputed; "full_keep_kernels" keeps the named
    # values alone, with the wide products of MATMUL_CAST_NAMES; "full" keeps
    # nothing (~a second forward in backward). A bool is accepted (True="full").
    remat: Any = "none"
    # lax.scan over stacked layer weights (one compiled block body, what
    # pipeline sharding needs) vs an unrolled Python loop (16x the HLO, but
    # activations save as distinct buffers, not dynamic-update-slice stacking).
    scan_layers: bool = True
    # Set (to the mesh axis name, 'seq') by the pipeline schedules when their
    # shard_map is manual over the sequence axis: activations carry LOCAL
    # chunks, attention runs the *_sharded ring/Ulysses bodies, positions are
    # offset by the shard index and dropout streams decorrelated a shard.
    seq_manual_axis: Optional[str] = None
    # Mixture-of-Experts MLP (0 = dense). When > 0 every block's MLP becomes
    # a top-k routed expert layer (models.moe) and the training loss gains
    # the Switch load-balance auxiliary term.
    n_experts: int = 0
    expert_top_k: int = 2
    # A number: each expert accepts at most ceil(factor * k * N / E) tokens and
    # drops the rest (the GELU toy; the 'expert'-axis path). None: dropless
    # routing, every assignment computed through grouped matmuls (OLMoE-class
    # SwiGLU experts; models.moe module docstring).
    capacity_factor: Optional[float] = 1.25
    router_aux_coef: float = 0.01
    # Renormalise each token's chosen gates to sum to 1 (the capacity path
    # always does). OLMoE does not: norm_topk_prob false.
    norm_topk_prob: bool = True
    # Router z-loss coefficient (mean over tokens of logsumexp(logits)^2;
    # dropless path only). The layer loop's one aux scalar carries it in
    # units of router_aux_coef, which must then be > 0.
    router_z_coef: float = 0.0
    # Zigzag causal load balancing on ring attention: None = auto (on for
    # causal rings with even local shards, ops/ring_attention.py), True = force
    # (errors when the geometry can't), False = the contiguous layout.
    ring_zigzag: Optional[bool] = None
    # Aux channel content: 'switch' (the load-balance loss term) or 'overflow'
    # (the fraction of assignments the capacity limit dropped: the
    # moe_overflow_fraction diagnostic, without a wider aux carry).
    moe_aux_mode: str = "switch"
    # Expert-parallel dispatch (the capacity path; models.moe's docstring):
    # 'auto' takes the explicit all-to-all shard_map path where an 'expert'
    # axis (>1) is in scope and the geometry allows, else the GSPMD einsums;
    # 'alltoall' forces the first (raises if it can't), 'einsum' the second.
    moe_dispatch: str = "auto"
    # ------------------------------------------------------------------
    # Architecture-family knobs (models.llama sets these; the defaults
    # reproduce the reference TinyGPT architecture bit-for-bit — reference
    # train_harness.py:36-131 has none of these options).
    # ------------------------------------------------------------------
    # Normalization: 'layernorm' (mean+var, learned scale/bias) or 'rmsnorm'
    # (no mean subtraction, scale only — Llama). Statistics always fp32.
    norm: str = "layernorm"
    norm_eps: float = 1e-5
    # Position information: 'learned' (additive wpe table, the reference
    # design), 'rope' (rotary embedding applied to q/k per head — no
    # positional parameters at all, and block_size no longer bounds the
    # table, only the benchmark geometry) or 'none' (no table and no rotation:
    # attention layers beside ``ssd`` ones, whose scan carries the order).
    pos_embed: str = "learned"
    rope_theta: float = 10000.0
    # MLP: 'gelu' (D -> mlp_dim -> exact-erf GELU -> D, the reference MLP),
    # 'swiglu' (gate/up pair, silu(gate)*up -> down — Llama) or 'relu2' (not
    # gated: D -> mlp_dim -> relu(.)^2 -> D, no bias — Nemotron-H; the dropless
    # routed experts' and their shared expert's only, leaves moe_wu / shared_wu).
    mlp_act: str = "gelu"
    # Hidden width of the MLP. None = 4*n_embd (the reference ratio). The
    # Llama family passes an explicit width (~8/3*D rounded for SwiGLU's
    # iso-parameter budget across its three matrices).
    mlp_hidden: Optional[int] = None
    # Grouped-query attention: number of K/V heads. None = n_head (MHA).
    # Each group of n_head/n_kv_head query heads shares one K/V head; the
    # projection splits into separate wq/wkv leaves (the fused wqkv layout
    # only exists for the square MHA case).
    n_kv_head: Optional[int] = None
    # Width of one head where it is not n_embd / n_head (Qwen3-MoE, SDAR: 32
    # heads of 128 over a hidden size of 2048, so q and the attention's output
    # are n_head * head_width = 4096 wide). Split projections only (n_kv_head).
    head_width: Optional[int] = None
    # QK-norm, leaves q_norm / k_norm, before rope. True (OLMoE): an RMSNorm
    # with its own learned scale over the whole projected q vector and over
    # the whole projected k vector, before the split into heads. "head"
    # (Qwen3-MoE, SDAR): over each head's head_dim, after the split, one
    # (head_dim,) scale for q's heads and one for k's.
    qk_norm: Any = False
    # Latent attention (MLA, DeepSeek-V2): set ``kv_lora_rank`` and the three
    # head widths. q is projected whole to heads of qk_nope + qk_rope; the
    # input is projected down to kv_lora_rank + qk_rope, the first part
    # RMS-normed (its own scale, leaf kv_norm) and expanded per head to
    # [k_nope | v], the last part one rotary key that every head shares.
    # Keys are qk_nope + qk_rope wide, values v_head_dim: the flash kernels
    # take both widths. Leaves wq / wkv_a / kv_norm / wkv_b; wo is
    # (n_head * v_head_dim, n_embd).
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN frequencies for the rotary part (None: plain rope_theta), and its
    # factor on the softmax scale.
    rope_scaling: Optional[YarnScaling] = None
    # Leading dense layers (DeepSeek first_k_dense_replace): the first
    # ``first_k_dense`` of the n_layer layers have a dense SwiGLU MLP of
    # width ``dense_mlp_hidden`` where the rest route; they are a stack of
    # their own, params['dense_blocks'], beside 'blocks' (n_layer -
    # first_k_dense layers).
    first_k_dense: int = 0
    dense_mlp_hidden: Optional[int] = None
    # Shared experts beside the routed sum: one SwiGLU of width
    # n_shared_experts * mlp_dim that every token passes (leaves shared_wgu /
    # shared_wd; scope 'shared').
    n_shared_experts: int = 0
    # The experts this chip holds, (first, count), of the n_experts the router
    # scores (dropless path): the leaves moe_wgu / moe_wd hold ``count``
    # experts, assignments on the others add nothing here (their chips add
    # them). None: all of them.
    experts_held: Optional[Tuple[int, int]] = None
    # Rows of the held experts' buffer, as a multiple of the expected N * K *
    # count / n_experts (rounded up to the grouped matmul's row tile). None:
    # N * K rows, which nothing can overflow. With a number the train step
    # returns, after its loss, (rows the buffers took, held assignments that
    # did not fit), summed over layers and micro-batches.
    held_rows_factor: Optional[float] = None
    # Load-balance term per sequence and averaged over sequences (DeepSeek
    # seq_aux), not over the whole batch.
    seq_aux: bool = False
    # The training objective where it is not cross-entropy at every position:
    # the stream, the mask rule and the loss of BlockDiffusionObjective. The
    # batch still holds clean documents of block_size tokens at most; forward
    # builds the stream of twice that from its key (its dropout_key, which the
    # train step folds from seed, step and micro-batch), and the step returns
    # the masked-token count after its loss (``step_report``).
    block_diffusion: Optional[BlockDiffusionObjective] = None
    # Layers of more than one kind in one stack (Gemma-2/3, Mellum-2: sliding
    # window layers beside global ones): one of LAYER_KINDS a layer, in order.
    # The kind is data on the layer, not a second stack: every layer has the
    # same leaves, and the layer loop hands each its kind statically, which
    # chooses its mask rule (``mask_rule``), its rotary table (``rotary``) and
    # its scope under ``attention``. None: every layer as ``causal`` says.
    layer_types: Optional[Tuple[str, ...]] = None
    # Keys a ``window`` layer's query sees, its own included.
    sliding_window: Optional[int] = None
    # ((kind, Rotary), ...) for the kinds whose table is not plain rope_theta.
    layer_rotary: Optional[Tuple[Tuple[str, Rotary], ...]] = None
    # ((kind, query heads), ...) for the attention kinds whose head count is
    # not n_head (Laguna: 48 on the full layers, 64 on the sliding ones, the
    # same KV heads and head width). The count decides the shapes of wq, wg
    # and wo, so kinds of unequal counts are stacks of their own
    # (``layer_groups``) and run unrolled in the published order.
    layer_heads: Optional[Tuple[Tuple[str, int], ...]] = None
    # A learned gate on the attention's output: sigmoid(h wg), one scalar a
    # head a token from the sublayer's normed input, times that head's output
    # before wo (leaf wg (D, heads), no bias; scope 'attn_gate').
    attn_gate: bool = False
    # A ``kda`` layer's sizes (``mixers/kda.py``): heads of kda_head_dim keys
    # and values, kda_conv taps behind each of q, k, v, chunks of kda_chunk
    # positions; the decay's and the gate's low-rank maps have rank kda_head_dim.
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_chunk: int = 128  # ops.kda.DEFAULT_CHUNK: measured there
    # An ``ssd`` layer's sizes (``mixers/ssd.py``): ssd_heads heads of
    # ssd_head_dim channels, B and C of ssd_state columns a group of ssd_groups,
    # ssd_conv taps over x | B | C, chunks of ssd_chunk positions.
    ssd_heads: int = 0
    ssd_head_dim: int = 0
    ssd_groups: int = 1
    ssd_state: int = 0
    ssd_conv: int = 4
    ssd_chunk: int = 128  # the family's published chunk_size
    # A ``conv`` layer's taps (``mixers/conv.py``; LFM2's conv_L_cache).
    conv_taps: int = 3
    # Each block of the stack is one sublayer alone behind its own norm and
    # residual (Nemotron-H's hybrid_override_pattern): a block of a mixer's kind
    # has no feed-forward part, and a block of kind ``mlp`` no mixer. The
    # stacks of the parameter tree are then by kind (``layer_groups``:
    # 'ssd_blocks', 'global_blocks', 'mlp_blocks'), each with the one norm its
    # half reads (ln1_scale a mixer's, ln2_scale the feed-forward part's).
    block_halves: bool = False
    # Width of the one shared expert where it is not n_shared_experts x mlp_dim
    # (Nemotron-H: moe_shared_expert_intermediate_size).
    shared_expert_hidden: Optional[int] = None
    # Latent attention without rotary on its qk_rope_head_dim columns (Kimi
    # Linear's mla_use_nope): the columns stay, nothing rotates them.
    mla_nope: bool = False
    # How the dropless router scores: 'softmax' over the experts, or 'sigmoid'
    # of each logit (DeepSeek-V3-class: the choice is by score + the leaf
    # router_bias, a buffer that gets no gradient; the gates are the scores at
    # the chosen, renormalised under norm_topk_prob; no load-balance term).
    router_score: str = "softmax"
    # What multiplies the gates after renormalisation.
    routed_scaling_factor: float = 1.0
    # Linear/LayerNorm biases (Llama ships none anywhere).
    bias: bool = True
    # Weight-tied LM head (reference train_harness.py:61-62). False adds a
    # separate 'lm_head' (V, D) leaf (Llama unties).
    tie_embeddings: bool = True
    # The step's placement decisions, set by the train step (train/step.py), not by
    # a model's author. block_grad_spec / block_param_spec: sorted tuples of
    # (block leaf name, PartitionSpec of one layer's slice) that the layer loops
    # apply to each layer's weights, the first to the COTANGENT (zero2: every
    # layer's grad reduce-scatter issues inside the backward loop), the second to
    # the weights at their use (fsdp / zero3: the all-gather issues per block); a
    # tuple so that the config stays hashable (`_constrain_layer`).
    block_grad_spec: Any = None
    block_param_spec: Any = None
    # A PartitionSpec for the (B, S, D) residual stream the layer scan carries:
    # pins the backward's stacked activation stash with it (`apply_blocks`).
    scan_carry_spec: Any = None
    # P(batch, seq, hidden) for the F-wide intermediates between the MLP's two
    # projections where the strategy shards the first projection's weight over
    # 'data' along F: the activations take the placement of the weight shards they
    # meet and only (B, S, D) travels (`_pin_mlp_hidden`; left to propagation,
    # mistral-7b.fsdp4 ran 48 all-to-alls a step at the crossings).
    mlp_hidden_spec: Any = None
    # Collective-matmul tp fusion (ops/collective_matmul.py): with a >1 'model'
    # axis in scope the tp projections run as shard_map-decomposed matmuls whose
    # all-gather / reduce-scatter chunks rotate by ppermute inside the dot, the
    # residual stream sequence-sharded over 'model'. --tp-collective-matmul.
    tp_collective_matmul: bool = False

    @property
    def head_dim(self) -> int:
        if self.head_width is not None:
            return self.head_width
        assert self.n_embd % self.n_head == 0
        return self.n_embd // self.n_head

    @property
    def kv_heads(self) -> int:
        return self.n_kv_head if self.n_kv_head is not None else self.n_head

    @property
    def mlp_dim(self) -> int:
        return self.mlp_hidden if self.mlp_hidden is not None else 4 * self.n_embd

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank is not None

    @property
    def qk_dim(self) -> int:
        """Width of one head's q and k."""
        if self.latent_attention:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_dim

    @property
    def v_dim(self) -> int:
        """Width of one head's v and output."""
        return self.v_head_dim if self.latent_attention else self.head_dim

    @property
    def attn_scale(self) -> Optional[float]:
        """The softmax scale where it is not 1 / sqrt(qk_dim), else None."""
        if self.latent_attention and self.rope_scaling is not None:
            return self.qk_dim ** -0.5 * self.rope_scaling.softmax_factor
        return None

    def mask_rule(self, stream_len: int, kind: Optional[str] = None):
        """The attention mask as ``ops.flash_attention.MaskRule``: ``causal``,
        a ``window`` layer's ``SlidingWindow``, or under ``block_diffusion``
        its rule over a stream of ``stream_len`` = 2L positions."""
        if kind == scopes.WINDOW:
            from ..ops.flash_attention import SlidingWindow

            return SlidingWindow(self.sliding_window)
        if self.block_diffusion is None:
            return self.causal
        from ..ops.flash_attention import BlockDiffusion

        if stream_len % 2:
            raise ValueError(f"a block-diffusion stream holds two copies; got {stream_len}")
        return BlockDiffusion(stream_len // 2, self.block_diffusion.block)

    def rotary(self, kind: Optional[str] = None) -> Rotary:
        """The rotary table of a layer of ``kind``."""
        return dict(self.layer_rotary or ()).get(kind, Rotary(self.rope_theta))

    @property
    def layer_period(self) -> int:
        """Layers after which ``layer_types`` repeats: what the scanned loop's
        body holds (1 for a stack of one kind)."""
        kinds = self.layer_types
        if kinds is None:
            return 1
        return next(p for p in range(1, len(kinds) + 1)
                    if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p))

    def heads(self, kind: Optional[str] = None) -> int:
        """Query heads of an attention layer of ``kind``."""
        return dict(self.layer_heads or ()).get(kind, self.n_head)

    @property
    def ssd_inner(self) -> int:
        """d_inner of an ``ssd`` layer: heads x head width."""
        return self.ssd_heads * self.ssd_head_dim

    @property
    def ssd_xbc(self) -> int:
        """Columns of an ``ssd`` layer's x | B | C, what its convolution spans."""
        return self.ssd_inner + 2 * self.ssd_groups * self.ssd_state

    def halves(self, kind: Optional[str]) -> Tuple[bool, bool]:
        """(a layer of ``kind`` has a mixer, it has a feed-forward part)."""
        if not self.block_halves:
            return True, True
        return kind != scopes.MLP, kind == scopes.MLP

    @property
    def shared_dim(self) -> int:
        """Width of the shared experts' one MLP."""
        return self.shared_expert_hidden or self.n_shared_experts * self.mlp_dim

    @property
    def heads_by_kind(self) -> bool:
        """Whether the stack's attention kinds differ in head count."""
        kinds = mixers.attention.own(self.layer_types or ())
        return len({self.heads(kind) for kind in kinds}) > 1

    @property
    def stacks_unequal(self) -> bool:
        """Whether the layers' leaves differ by kind (another mixer, another
        head count, a half alone): such stacks run unrolled through
        ``_apply_stacks``."""
        return mixers.own_leaves(self.layer_types) or self.heads_by_kind or self.block_halves

    @property
    def layer_groups(self) -> Tuple[Tuple[str, Tuple[int, ...]], ...]:
        """((a stack's name in the parameter tree, its layers' indices in the
        published order), ...): the layers of equal leaves, by what decides
        the leaves' shapes: the mixer, the MLP and, where the attention kinds
        differ in head count, the kind."""
        groups: Dict[str, list] = {}
        by_kind = self.heads_by_kind
        if self.block_halves:  # a block is one half alone: its kind decides its leaves
            for i, kind in enumerate(self.layer_types):
                groups.setdefault(f"{kind}_blocks", []).append(i)
            return tuple((name, tuple(layers)) for name, layers in groups.items())
        for i in range(self.n_layer):
            kind = None if self.layer_types is None else self.layer_types[i]
            name = mixers.stack_name(kind, i < self.first_k_dense)
            by_its_kind = by_kind and name in mixers.attention.STACKS
            groups.setdefault(f"{kind}_{name}" if by_its_kind else name, []).append(i)
        return tuple((name, tuple(layers)) for name, layers in groups.items())

    @property
    def aux_shape(self) -> Tuple[int, ...]:
        """The layer loop's aux carry: the load-balance scalar, or with it the
        held experts' rows and the held assignments that did not fit."""
        return (3,) if self.reports_held_overflow else ()

    @property
    def n_mlp_layers(self) -> int:
        """Layers with a feed-forward part: all of them, or under
        ``block_halves`` the blocks of kind ``mlp``."""
        return self.layer_types.count(scopes.MLP) if self.block_halves else self.n_layer

    @property
    def n_moe_layers(self) -> int:
        return self.n_mlp_layers - self.first_k_dense if self.n_experts > 0 else 0

    @property
    def n_experts_held(self) -> int:
        return self.n_experts if self.experts_held is None else self.experts_held[1]

    @property
    def trains_routing(self) -> bool:
        """Whether the gates and the load-balance term are differentiated. A
        chip that holds a part of the experts sees the gradient through the
        gates of its own experts only (the sum over the chips that share the
        layer belongs to the 'expert' axis exchange, which is not written);
        applied alone it pulls every token onto the held experts within tens
        of steps (PERF.md, PR 30). So a part of the experts does not train its
        routing: gates and the load-balance term are constants of its backward
        pass; the experts, and everything else, train."""
        return self.experts_held is None or self.experts_held[1] == self.n_experts

    @property
    def reports_held_overflow(self) -> bool:
        return self.experts_held is not None and self.held_rows_factor is not None

    @property
    def step_report(self) -> Tuple[str, ...]:
        """What the train step returns after its loss, one float32 each, summed
        over layers and micro-batches; empty for a config that reports nothing."""
        held = ("held_rows", "held_overflow") if self.reports_held_overflow else ()
        return held + (("masked_tokens",) if self.block_diffusion is not None else ())

    def refuse_pipeline(self) -> None:
        """The pipeline schedules slice one homogeneous stack and run the
        sharded attention bodies; they do not slice this."""
        if self.first_k_dense or self.latent_attention:
            raise ValueError(
                "the pipeline schedules take one homogeneous stack of blocks with "
                f"ordinary attention; got first_k_dense={self.first_k_dense}, "
                f"kv_lora_rank={self.kv_lora_rank} (latent attention). Run this "
                "config with pipe=1"
            )
        if self.block_diffusion is not None:
            raise ValueError(
                "the pipeline schedules run next-token stages; block diffusion "
                "builds its stream and weighs its loss in forward(). Run this "
                "config with pipe=1"
            )
        if self.layer_types is not None:
            raise ValueError(
                "the pipeline schedules slice one homogeneous stack; layer_types gives each "
                "layer a kind of its own (sliding_window or kda layers beside global ones, "
                "conv or ssd layers, stacks of unequal leaves under layer_heads or "
                "block_halves). Run this config with pipe=1")

    def __post_init__(self):
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"norm must be 'layernorm'|'rmsnorm', got {self.norm!r}")
        if self.pos_embed not in ("learned", "rope", "none"):
            raise ValueError(
                f"pos_embed must be 'learned'|'rope'|'none', got {self.pos_embed!r}"
            )
        if self.mlp_act not in ("gelu", "swiglu", "relu2"):
            raise ValueError(
                f"mlp_act must be 'gelu'|'swiglu'|'relu2', got {self.mlp_act!r}")
        if self.mlp_act == "relu2" and (
                self.bias or self.tp_collective_matmul or self.n_experts == 0
                or self.capacity_factor is not None):
            raise ValueError(
                "mlp_act='relu2' (W_down relu(W_up h)^2, no bias) is the dropless routed "
                "experts' and their shared expert's (n_experts > 0, capacity_factor=None), "
                "without tp_collective_matmul; a dense MLP of it is not built")
        if self.pos_embed == "none" and (
                self.latent_attention or self.layer_rotary is not None
                or self.seq_manual_axis is not None):
            raise ValueError(
                "pos_embed='none' (attention without a table or a rotation: the layers "
                "beside it carry the order) is ordinary attention's, without layer_rotary "
                "(latent attention has mla_nope) and outside the sequence-parallel pipeline")
        if self.n_kv_head is not None and self.n_head % self.n_kv_head != 0:
            raise ValueError(
                f"n_kv_head={self.n_kv_head} must divide n_head={self.n_head}"
            )
        dropless = self.capacity_factor is None
        if self.n_experts > 0 and dropless != (
                self.mlp_act in ("swiglu", "relu2") and not self.bias):
            raise ValueError(
                "MoE blocks come in two kinds: GELU experts with biases under a "
                "capacity_factor, and SwiGLU or relu2 experts without bias under dropless "
                f"routing (capacity_factor=None); got mlp_act={self.mlp_act!r}, "
                f"bias={self.bias}, capacity_factor={self.capacity_factor!r}"
            )
        if (self.router_z_coef or not self.norm_topk_prob) and not dropless:
            raise ValueError(
                "router_z_coef and norm_topk_prob=False belong to dropless routing "
                "(capacity_factor=None); the capacity path renormalises its gates "
                "and has no z-loss"
            )
        if self.router_z_coef and not self.router_aux_coef > 0:
            raise ValueError(
                "router_z_coef rides the aux channel in units of router_aux_coef, "
                "which must be > 0"
            )
        if self.latent_attention and not (
            self.pos_embed == "rope" and not self.bias and not self.qk_norm
            and self.n_kv_head is None and self.qk_nope_head_dim > 0
            and self.qk_rope_head_dim > 0 and self.v_head_dim > 0
            and self.attention_impl in ("flash", "reference")
            and not self.tp_collective_matmul
        ):
            raise ValueError(
                "latent attention (kv_lora_rank) needs pos_embed='rope', bias=False, "
                "no qk_norm, no n_kv_head, the three head widths, attention_impl "
                "'flash' or 'reference' and no tp_collective_matmul"
            )
        if self.rope_scaling is not None and not self.latent_attention:
            raise ValueError("rope_scaling (YaRN) is wired for latent attention only")
        if self.first_k_dense and not (
            0 < self.first_k_dense < self.n_layer and self.n_experts > 0
            and self.mlp_act == "swiglu" and not self.bias
            and self.dense_mlp_hidden
        ):
            raise ValueError(
                "first_k_dense leading layers are dense SwiGLU layers (no bias) of "
                "width dense_mlp_hidden before routed ones: 0 < first_k_dense < n_layer"
            )
        if (self.n_shared_experts or self.experts_held is not None
                or self.seq_aux) and not (self.n_experts > 0 and dropless):
            raise ValueError(
                "n_shared_experts, experts_held and seq_aux belong to dropless routing"
            )
        if self.shared_expert_hidden is not None and not self.n_shared_experts:
            raise ValueError("shared_expert_hidden is the width of n_shared_experts' one MLP")
        if self.experts_held is not None:
            first, count = self.experts_held
            if not (0 <= first and 0 < count and first + count <= self.n_experts):
                raise ValueError(
                    f"experts_held={self.experts_held} must lie inside the "
                    f"{self.n_experts} experts the router scores"
                )
        if self.held_rows_factor is not None and self.experts_held is None:
            raise ValueError("held_rows_factor sizes the buffer of experts_held")
        if self.head_width is not None and (
                self.kv_heads == self.n_head or self.latent_attention
                or self.tp_collective_matmul):
            raise ValueError(
                "head_width (heads that are not n_embd / n_head wide) is wired for the "
                "split q and k/v projections of n_kv_head < n_head, without "
                "tp_collective_matmul"
            )
        if self.qk_norm not in (False, True, "head"):
            raise ValueError(f"qk_norm must be False|True|'head', got {self.qk_norm!r}")
        kinds = self.layer_types
        if kinds is not None:
            if len(kinds) != self.n_layer or any(k not in LAYER_KINDS for k in kinds):
                raise ValueError(
                    f"layer_types names one of {LAYER_KINDS} for each of the "
                    f"{self.n_layer} layers; got {kinds}"
                )
            if self.attention_impl not in ("flash", "reference") or (
                    self.seq_manual_axis is not None):
                raise ValueError(
                    "layer_types (sliding_window or kda layers beside global ones, conv or "
                    "ssd layers, head counts by kind, blocks of one half) runs "
                    "attention_impl 'flash' or 'reference' on whole sequences: ring "
                    "attention, Ulysses and the sequence-parallel pipeline cut the "
                    "sequence, and their bodies take causal or no mask only; got "
                    f"attention_impl={self.attention_impl!r}, "
                    f"seq_manual_axis={self.seq_manual_axis!r}"
                )
            if not self.causal or self.block_diffusion is not None or (
                    self.latent_attention and scopes.WINDOW in kinds) or (
                    self.first_k_dense and not self.stacks_unequal):
                raise ValueError(
                    "layer_types mixes causal layers in one stack: causal=True, no "
                    "block_diffusion; latent attention (kv_lora_rank) is its 'global' "
                    "layers' and has no 'window' ones; first_k_dense leading layers "
                    "go with 'kda' layers, 'conv' layers or head counts by kind "
                    "(layer_heads): the stacks of unequal leaves"
                )
            mixers.check(self)
            if (scopes.MLP in kinds) != self.block_halves or (self.block_halves and (
                    self.scan_layers or self.first_k_dense or self.layer_heads is not None
                    or scopes.MLP not in kinds or self.tp_collective_matmul)):
                raise ValueError(
                    "block_halves (each block a mixer or a feed-forward part alone) goes "
                    "with layer_types that name the 'mlp' blocks, and a kind 'mlp' with "
                    "block_halves: stacks by kind, run unrolled in the published order "
                    "(scan_layers=False: the scanned loop is refused), without first_k_dense, "
                    "layer_heads or tp_collective_matmul; got "
                    f"block_halves={self.block_halves}, layer_types={kinds}"
                )
        elif self.block_halves:
            raise ValueError("block_halves needs layer_types: a kind for each block")
        if self.layer_heads is not None:
            if kinds is None or self.latent_attention or self.n_kv_head is None or (
                    self.tp_collective_matmul) or any(
                    k not in kinds or k not in mixers.attention.KINDS or n < 1 or n % self.kv_heads
                    for k, n in self.layer_heads):
                raise ValueError(
                    "layer_heads gives ((kind, query heads), ...) for attention kinds of "
                    "layer_types, each a multiple of n_kv_head, over the split q and k/v "
                    "projections (n_kv_head), without latent attention or "
                    f"tp_collective_matmul; got {self.layer_heads}, layer_types={kinds}"
                )
            if self.heads_by_kind and self.scan_layers:
                raise ValueError(
                    "layer_heads gives the attention kinds unequal head counts, so wq, wg "
                    "and wo differ in shape by kind: stacks of unequal leaves run unrolled, "
                    "in the published order (scan_layers=False), and the scanned loop is "
                    "refused"
                )
        if self.attn_gate and (self.latent_attention or self.tp_collective_matmul):
            raise ValueError(
                "attn_gate (the per-head sigmoid gate on the attention's output) is "
                "wired for ordinary attention without tp_collective_matmul"
            )
        if self.router_score not in ("softmax", "sigmoid") or (
                self.router_score == "sigmoid" or self.routed_scaling_factor != 1.0
        ) and not (self.n_experts > 0 and dropless):
            raise ValueError(
                "router_score is 'softmax' or 'sigmoid'; 'sigmoid' and "
                "routed_scaling_factor belong to dropless routing; got "
                f"{self.router_score!r}, {self.routed_scaling_factor}"
            )
        if self.mla_nope and not self.latent_attention:
            raise ValueError("mla_nope is latent attention's (kv_lora_rank)")
        if (scopes.WINDOW in (kinds or ())) != (self.sliding_window is not None) or (
                self.sliding_window is not None and self.sliding_window < 1):
            raise ValueError(
                "sliding_window (>= 1 keys, the query's own included) is what a "
                f"'window' layer of layer_types sees; got sliding_window="
                f"{self.sliding_window}, layer_types={kinds}"
            )
        if self.layer_rotary is not None and (
                self.pos_embed != "rope" or kinds is None
                or any(k not in kinds or not isinstance(r, Rotary)
                       for k, r in self.layer_rotary)):
            raise ValueError(
                "layer_rotary gives ((kind, Rotary), ...) for kinds of layer_types "
                f"under pos_embed='rope'; got {self.layer_rotary}"
            )
        for _, r in self.layer_rotary or ():
            if r.rotary_dim is not None and not (
                    0 < r.rotary_dim <= self.head_dim and r.rotary_dim % 2 == 0
                    and not self.latent_attention):
                raise ValueError(
                    f"rotary_dim (the leading lanes of a head that rotate) is even and at "
                    f"most head_dim={self.head_dim}, for ordinary attention; got {r.rotary_dim}"
                )
        bd = self.block_diffusion
        if bd is not None:
            if self.attention_impl not in ("flash", "reference") or (
                    self.seq_manual_axis is not None):
                raise ValueError(
                    "block diffusion runs attention_impl 'flash' or 'reference' on "
                    "whole streams: ring attention, Ulysses and the sequence-parallel "
                    "pipeline cut the sequence, and their bodies take causal or no "
                    f"mask only; got attention_impl={self.attention_impl!r}, "
                    f"seq_manual_axis={self.seq_manual_axis!r}"
                )
            if self.causal or self.pos_embed != "rope":
                raise ValueError(
                    "block diffusion brings its own mask rule (causal=False) and "
                    "rotates both copies at positions 0..L-1 (pos_embed='rope')"
                )
            if not (bd.block > 0 and 0 <= bd.mask_id < self.vocab_size
                    and 0.0 < bd.t_min <= bd.t_max <= 1.0):
                raise ValueError(
                    f"{bd}: block > 0, mask_id inside the vocabulary of "
                    f"{self.vocab_size}, 0 < t_min <= t_max <= 1"
                )


def get_model_config(tier: str, seq_len: int, **overrides) -> TinyGPTConfig:
    """Model tier table (parity: reference train_harness.py:157-179).

    block_size = seq_len exactly as the reference sets it (:168, :176), so the
    positional table is sized to the benchmarked sequence.
    """
    tiers = {
        # ~236M params (tied embeddings) — the tier all published numbers used.
        "A": dict(vocab_size=32000, n_embd=1024, n_head=16, n_layer=16),
        # ~1.68B params — stress tier.
        "B": dict(vocab_size=32000, n_embd=2048, n_head=32, n_layer=32),
        # Ours: tiny tier for CPU tests / CI smoke. Not in the reference.
        "S": dict(vocab_size=512, n_embd=128, n_head=4, n_layer=2),
    }
    if tier not in tiers:
        raise ValueError(f"Unknown tier: {tier!r} (expected one of {sorted(tiers)})")
    kw = dict(tiers[tier])
    kw["block_size"] = seq_len
    kw.update(overrides)
    return TinyGPTConfig(**kw)


# Logical axis names for every parameter leaf, used by parallel.strategies to
# turn a strategy into per-leaf PartitionSpecs. Leaves under 'blocks' carry a
# leading 'layers' axis (the scan axis).
PARAM_AXIS_RULES: Dict[str, Tuple[Optional[str], ...]] = {
    "wte": ("vocab", "embed"),
    "wpe": ("pos", "embed"),
    "blocks/ln1_scale": ("layers", "embed"),
    "blocks/ln1_bias": ("layers", "embed"),
    # every mixer's leaves, by its module (a stack by any name takes them as 'blocks')
    **mixers.AXIS_RULES,
    "blocks/ln2_scale": ("layers", "embed"),
    "blocks/ln2_bias": ("layers", "embed"),
    "blocks/wfc": ("layers", "embed", "mlp"),
    "blocks/bfc": ("layers", "mlp"),
    "blocks/wproj": ("layers", "mlp", "embed"),
    "blocks/bproj": ("layers", "embed"),
    # SwiGLU variant (present instead of wfc/bfc when mlp_act='swiglu'):
    # gate and up matrices stack on a 'gate2' axis; wproj/bproj are shared
    # with the dense path (same (layers, mlp, embed) shape).
    "blocks/wgu": ("layers", "embed", "gate2", "mlp"),
    "blocks/bgu": ("layers", "gate2", "mlp"),
    # MoE variant (present instead of wfc/bfc/wproj/bproj when n_experts > 0)
    "blocks/router": ("layers", "embed", "experts"),
    "blocks/moe_w1": ("layers", "experts", "embed", "mlp"),
    "blocks/moe_b1": ("layers", "experts", "mlp"),
    "blocks/moe_w2": ("layers", "experts", "mlp", "embed"),
    "blocks/moe_b2": ("layers", "experts", "embed"),
    # Dropless SwiGLU experts (present instead of moe_w1..b2 when
    # capacity_factor is None). Gate and up share one matrix's columns (F of
    # gate, then F of up): one grouped matmul, and no relayout of 268M
    # parameters a step, which a (.., 2, F) pair of minor axes costs on a TPU.
    "blocks/moe_wgu": ("layers", "experts", "embed", "gate_up"),
    "blocks/moe_wd": ("layers", "experts", "mlp", "embed"),
    # Shared experts (present beside router / moe_wgu / moe_wd when
    # n_shared_experts): one SwiGLU, gate columns then up columns.
    "blocks/shared_wgu": ("layers", "embed", "gate_up"),
    "blocks/shared_wd": ("layers", "mlp", "embed"),
    # The sigmoid router's selection bias (present when router_score='sigmoid'):
    # a buffer, added to the scores for the choice only; no gradient reaches it.
    "blocks/router_bias": ("layers", "experts"),
    # Experts that are not gated (mlp_act='relu2', present instead of moe_wgu /
    # shared_wgu): the up projection alone.
    "blocks/moe_wu": ("layers", "experts", "embed", "mlp"),
    "blocks/shared_wu": ("layers", "embed", "mlp"),
    "lnf_scale": ("embed",),
    "lnf_bias": ("embed",),
    # Untied LM head (present when tie_embeddings=False): same logical axes
    # as wte, so TP's vocab sharding (Megatron parallel softmax) applies to
    # both ends identically.
    "lm_head": ("vocab", "embed"),
}


def init_params(config: TinyGPTConfig, key: jax.Array) -> Params:
    """Initialize the parameter pytree.

    Init scheme parity (reference ``_init_weights``, train_harness.py:69-80):
    normal(0, 0.02) for linear/embedding weights, zeros for biases, ones/zeros
    for LayerNorm scale/bias. The LM head is weight-tied to ``wte`` (reference
    ``train_harness.py:61-62``) — there is no separate head matrix at all.
    """
    c = config
    D, H, L, V, T = c.n_embd, c.n_head, c.n_layer, c.vocab_size, c.block_size
    F, Hkv, Dh = c.mlp_dim, c.kv_heads, c.head_dim
    # The legacy tree (fused qkv, tied head, learned positions) splits into
    # exactly 8 keys — pinned so every published artifact's init (and loss
    # trace) stays bit-reproducible. Family configs with extra leaves use a
    # wider split; they are new surface with no reproduction constraint.
    legacy = Hkv == H and c.tie_embeddings and c.pos_embed == "learned"
    wide = c.latent_attention or c.first_k_dense or c.n_shared_experts
    k = iter(jax.random.split(
        key, 64 if c.stacks_unequal else 8 if legacy else 24 if wide else 12))

    normal = functools.partial(_normal, c)
    zeros = lambda shape: jnp.zeros(shape, c.param_dtype)
    ones = lambda shape: jnp.ones(shape, c.param_dtype)

    def mlp_leaves(L):
        """One stack's MLP as the config has it (routed where n_experts), L layers."""
        blocks = {"ln2_scale": ones((L, D))} if c.block_halves else {}
        if c.n_experts > 0:
            E = c.n_experts
            blocks["router"] = normal(next(k), (L, D, E))
            if c.router_score == "sigmoid":
                blocks["router_bias"] = zeros((L, E))
            if c.capacity_factor is None:  # dropless SwiGLU experts, no bias
                held = c.n_experts_held  # the router scores E; this chip's leaves hold these
                gated = c.mlp_act == "swiglu"  # else relu2: the up projection alone
                up, up_width = ("wgu", 2) if gated else ("wu", 1)
                blocks.update({
                    f"moe_{up}": normal(next(k), (L, held, D, up_width * F)),
                    "moe_wd": normal(next(k), (L, held, F, D)),
                })
                if c.n_shared_experts:
                    Fs = c.shared_dim
                    blocks.update({
                        f"shared_{up}": normal(next(k), (L, D, up_width * Fs)),
                        "shared_wd": normal(next(k), (L, Fs, D)),
                    })
            else:
                blocks.update(
                    moe_w1=normal(next(k), (L, E, D, F)),
                    moe_b1=zeros((L, E, F)),
                    moe_w2=normal(next(k), (L, E, F, D)),
                    moe_b2=zeros((L, E, D)),
                )
        elif c.mlp_act == "swiglu":
            blocks["wgu"] = normal(next(k), (L, D, 2, F))
            blocks["wproj"] = normal(next(k), (L, F, D))
            if c.bias:
                blocks["bgu"] = zeros((L, 2, F))
                blocks["bproj"] = zeros((L, D))
        else:
            blocks["wfc"] = normal(next(k), (L, D, F))
            blocks["wproj"] = normal(next(k), (L, F, D))
            if c.bias:
                blocks["bfc"] = zeros((L, F))
                blocks["bproj"] = zeros((L, D))
        return blocks

    def stack(name, layers):
        """The stack ``name`` (``layer_groups``) of these layers: its mixer's
        leaves, then its MLP's; the draws in that order."""
        L = len(layers)
        kind = c.layer_types[layers[0]] if c.layer_types else None
        if kind == scopes.MLP:  # under block_halves: the feed-forward part alone
            return mlp_leaves(L)
        leaves = mixers.of(kind).leaves(c, k, L, kind)
        if c.block_halves:  # the mixer alone
            return leaves
        if name.endswith("dense_blocks"):
            Fd = c.dense_mlp_hidden
            leaves.update(wgu=normal(next(k), (L, D, 2, Fd)), wproj=normal(next(k), (L, Fd, D)))
        else:
            leaves.update(mlp_leaves(L))
        return leaves

    # The draws' order is the seeds' contract with every published artifact:
    # 'blocks' (the layers after the leading dense ones), the embedding and the
    # head, 'dense_blocks', the stacks by a mixer's name (``mixers.STACKS``), then the stacks
    # named by kind (``layer_heads``, ``block_halves``) in the order of their first layers.
    groups = dict(c.layer_groups)
    params = {}
    if "blocks" in groups:
        params["blocks"] = stack("blocks", groups["blocks"])
    params.update(wte=normal(next(k), (V, D)), lnf_scale=ones((D,)))
    if c.pos_embed == "learned":
        params["wpe"] = normal(next(k), (T, D))
    if c.norm == "layernorm":
        params["lnf_bias"] = zeros((D,))
    if not c.tie_embeddings:
        params["lm_head"] = normal(next(k), (V, D))
    drawn_here = mixers.STACKS[1:]  # all but 'blocks', drawn above
    for name in (*drawn_here, *(n for n in groups if n not in mixers.STACKS)):
        if name in groups:
            params[name] = stack(name, groups[name])
    return params


def count_params(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _with_cotangent_spec(spec, x):
    """Identity whose COTANGENT is constrained to ``spec``.

    Wrapping a layer's weights with this inside the layer loop makes that
    layer's gradient adopt its target (ZeRO-2 sharded) placement at the
    point it is produced — inside the backward scan/loop body — so the
    reduce-scatter can overlap the next layer's backward compute instead
    of queueing in a tail bundle (see TinyGPTConfig.block_grad_spec).
    """
    return x


def _wcs_fwd(spec, x):
    return x, None


def _wcs_bwd(spec, _res, g):
    return (lax.with_sharding_constraint(g, spec),)


_with_cotangent_spec.defvjp(_wcs_fwd, _wcs_bwd)


def _apply_leaf_specs(layer: Params, spec_table: Any, wrap) -> Params:
    """Apply a (leaf name, spec) table to one layer's weight slice via
    ``wrap(spec, leaf)`` — leaves without an entry pass through untouched;
    an unset table is an exact no-op. The one iteration both per-block
    placement hooks share."""
    if not spec_table:
        return layer
    specs = dict(spec_table)
    return {
        k: (wrap(specs[k], v) if k in specs else v)
        for k, v in layer.items()
    }


def _constrain_layer_grads(config: TinyGPTConfig, layer: Params) -> Params:
    """Apply ``config.block_grad_spec`` to one layer's weight slice: the
    COTANGENT constraint (zero2 per-block grad placement)."""
    return _apply_leaf_specs(layer, config.block_grad_spec, _with_cotangent_spec)


def _constrain_layer_params(config: TinyGPTConfig, layer: Params) -> Params:
    """Apply ``config.block_param_spec`` to one layer's weight slice: a
    PRIMAL sharding constraint pinning the slice to its sharded
    (fsdp/zero3) placement at the point of use, so the all-gather the
    block's matmuls need issues inside the layer loop instead of bundling
    ahead of the stack. The constraint's transpose places the cotangent
    identically — the per-block grad layout for free."""
    return _apply_leaf_specs(
        layer, config.block_param_spec,
        lambda spec, v: lax.with_sharding_constraint(v, spec),
    )


def _constrain_layer(config: TinyGPTConfig, layer: Params) -> Params:
    """Both per-block placement hooks, primal (block_param_spec) inside the
    cotangent wrap (block_grad_spec) — strategies arm at most one today."""
    return _constrain_layer_grads(
        config, _constrain_layer_params(config, layer)
    )


def _block(
    config: TinyGPTConfig,
    x: jax.Array,  # (B, S, D) compute dtype
    layer: Params,  # one layer's slice of the stacked block params
    dropout_key: Optional[jax.Array],
    deterministic: bool,
    kind: Optional[str] = None,
    qk_tables: Optional[Dict] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Pre-LN transformer block -> (x, aux) where aux is the MoE load-balance
    loss contribution (0 for dense blocks). ``kind`` is the layer's, of
    ``config.layer_types``, static: its attention runs under a scope of that
    name, with that kind's mask rule and rotary table. ``qk_tables``:
    ``qk_prologue_tables``, made once for the stack.

    Parity: reference train_harness.py:108-131 for the dense path."""
    c = config
    keys = (
        jax.random.split(dropout_key, 2) if dropout_key is not None else (None, None)
    )
    if keys[1] is not None and c.seq_manual_axis is not None:
        # Sequence shards hold different token positions: decorrelate the
        # (materialized-mask) MLP dropout stream per shard. The attention key
        # keys[0] stays shared — ring/Ulysses handle their own coordinates.
        keys = (keys[0], jax.random.fold_in(keys[1], lax.axis_index(c.seq_manual_axis)))

    # The collective-matmul helpers fall back to the plain einsum without a >1
    # 'model' axis; inside the pipeline's sequence-manual region the stream is
    # already manual over 'seq', so the knob is refused there.
    if c.tp_collective_matmul and c.seq_manual_axis is not None:
        raise ValueError(
            "tp_collective_matmul cannot run inside a sequence-manual "
            "pipeline region (the residual stream is already sharded "
            "over the manual 'seq' axis; drop --tp-collective-matmul "
            "for pipeline arms)"
        )
    x = _mixer_half(c, x, layer, keys[0], deterministic, kind, qk_tables)
    return _mlp_half(c, x, layer, keys[1], deterministic)


def _mixer_half(c, x, layer, key, deterministic, kind, qk_tables):
    """``_block``'s first half under its scopes: the kind chooses the mixer
    (``mixers.MIXERS``), and every mixer is handed the same arguments."""
    own_scope = jax.named_scope(kind) if kind is not None else contextlib.nullcontext()
    with jax.named_scope(scopes.ATTENTION), own_scope:
        return mixers.of(kind).sublayer(c, x, layer, key, deterministic, kind, qk_tables)


def _mlp_half(c, x, layer, key, deterministic):
    """``_block``'s second half under its scope -> (x, aux)."""
    with jax.named_scope(scopes.MLP):
        return _mlp_sublayer(c, x, layer, key, deterministic)


def _pin_mlp_hidden(c: TinyGPTConfig, h: jax.Array) -> jax.Array:
    """Pin an F-wide MLP intermediate, (B, S, F) or (B, S, 2, F), to
    ``config.mlp_hidden_spec``; an unset spec is an exact no-op."""
    if c.mlp_hidden_spec is None:
        return h
    batch, seq, hidden = c.mlp_hidden_spec
    return lax.with_sharding_constraint(
        h, P(batch, seq, *(None,) * (h.ndim - 3), hidden)
    )


def _mlp_sublayer(
    c: TinyGPTConfig,
    x: jax.Array,
    layer: Params,
    dropout_key: Optional[jax.Array],
    deterministic: bool,
) -> Tuple[jax.Array, jax.Array]:
    """Norm -> MLP -> dropout -> residual, with the MoE aux: the second half
    of ``_block``. Dense D -> mlp_dim -> GELU(exact) -> D, SwiGLU
    (silu(gate)*up -> down), or the routed expert layer."""
    cd = c.compute_dtype
    use_cmm = c.tp_collective_matmul
    if use_cmm:
        from ..ops import collective_matmul as _cm

    h = _norm(c, x, layer["ln2_scale"], layer.get("ln2_bias"))
    if "router" in layer:  # every layer of 'blocks' when n_experts > 0
        from .moe import moe_mlp

        h, aux = moe_mlp(c, layer, h, dropout_key, deterministic)
        return x + h, aux
    if c.mlp_act == "swiglu":
        if use_cmm:
            gu = _cm.ag_proj(h, layer["wgu"].astype(cd)).astype(cd)
        else:
            gu = jnp.einsum(
                "bsd,dcf->bscf", h, layer["wgu"].astype(cd), preferred_element_type=jnp.float32
            ).astype(cd)
        gu = _pin_mlp_hidden(c, gu)
        if "bgu" in layer:
            gu = gu + layer["bgu"].astype(cd)
        gu = checkpoint_name(gu, MLP_GU)
        h = jax.nn.silu(gu[:, :, 0]) * gu[:, :, 1]
    else:
        if use_cmm:
            h = _cm.ag_proj(h, layer["wfc"].astype(cd)).astype(cd)
        else:
            h = jnp.einsum(
                "bsd,df->bsf", h, layer["wfc"].astype(cd), preferred_element_type=jnp.float32
            ).astype(cd)
        h = _pin_mlp_hidden(c, h)
        if "bfc" in layer:
            h = h + layer["bfc"].astype(cd)
        h = jax.nn.gelu(h, approximate=False)  # torch nn.GELU default is exact erf
    h = _pin_mlp_hidden(c, h)
    if use_cmm:
        h = _cm.rs_proj(h, layer["wproj"].astype(cd)).astype(cd)
    else:
        h = jnp.einsum(
            "bsf,fd->bsd", h, layer["wproj"].astype(cd), preferred_element_type=jnp.float32
        ).astype(cd)
    if "bproj" in layer:
        h = h + layer["bproj"].astype(cd)
    h = _dropout(h, c.dropout, dropout_key, deterministic)
    return x + h, jnp.zeros(c.aux_shape, jnp.float32)


@jax.named_scope(scopes.EMBED)
def embed(
    config: TinyGPTConfig,
    params: Params,
    idx: jax.Array,  # (B, S) int32
    dropout_key: Optional[jax.Array] = None,
    deterministic: bool = True,
) -> jax.Array:
    """Token + positional embedding -> dropout -> (B, S, D) compute dtype.

    Under a sequence-manual pipeline (``config.seq_manual_axis``), ``idx`` is
    this shard's chunk of the sequence: the positional table is sliced at the
    shard's global offset and the embedding-dropout stream is decorrelated
    per shard.
    """
    c = config
    S = idx.shape[1]
    tok = jnp.take(params["wte"], idx, axis=0)
    if c.seq_manual_axis is not None:
        shard = lax.axis_index(c.seq_manual_axis)
        if dropout_key is not None:
            dropout_key = jax.random.fold_in(dropout_key, shard)
    if c.pos_embed != "learned":
        # Rotary positions are applied to q/k inside each block (_rope in
        # _block), or none at all ('none'); the residual stream carries no
        # additive position signal.
        x = tok.astype(c.compute_dtype)
    else:
        if c.seq_manual_axis is not None:
            pos = lax.dynamic_slice_in_dim(params["wpe"], shard * S, S, axis=0)
        else:
            pos = params["wpe"][:S]
        x = (tok + pos[None, :, :]).astype(c.compute_dtype)
    if dropout_key is not None and not deterministic:
        x = _dropout(x, c.dropout, dropout_key, deterministic)
    return x


def _noise_and_dropout_keys(key: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The two halves of the key ``forward`` is given under block diffusion."""
    noise_key, dropout_key = jax.random.split(key)
    return noise_key, dropout_key


def bd_noise(
    config: TinyGPTConfig, key: jax.Array, shape: Tuple[int, int]
) -> Tuple[jax.Array, jax.Array]:
    """The noise of one (B, L) batch of documents under ``block_diffusion``,
    from the key ``forward`` is given -> (t (B, L // block) float32, one level
    a block, uniform on [t_min, t_max]; masked (B, L) bool, each token masked
    with its block's probability). ``forward`` draws exactly this: a check
    hands it to a reference that draws nothing."""
    bd = config.block_diffusion
    B, L = shape
    if L % bd.block:
        raise ValueError(f"documents of {L} tokens are not whole blocks of {bd.block}")
    t_key, token_key = jax.random.split(_noise_and_dropout_keys(key)[0])
    t = jax.random.uniform(
        t_key, (B, L // bd.block), jnp.float32, minval=bd.t_min, maxval=bd.t_max
    )
    masked = jax.random.uniform(token_key, (B, L), jnp.float32) < jnp.repeat(t, bd.block, axis=1)
    return t, masked


@jax.named_scope(scopes.EMBED)
@jax.named_scope(scopes.NOISE)
def bd_stream(
    config: TinyGPTConfig, idx: jax.Array, key: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(B, L) clean documents -> (the (B, 2L) stream [x_t ; x], the (B, L)
    loss weights 1 / t of the masked positions and 0 elsewhere, the masked
    positions), with ``bd_noise``'s noise."""
    bd = config.block_diffusion
    t, masked = bd_noise(config, key, idx.shape)
    noisy = jnp.where(masked, jnp.asarray(bd.mask_id, idx.dtype), idx)
    weights = jnp.where(masked, 1.0 / jnp.repeat(t, bd.block, axis=1), 0.0)
    return jnp.concatenate([noisy, idx], axis=1), weights, masked


def apply_blocks(
    config: TinyGPTConfig,
    blocks: Params,  # stacked block params, leading 'layers' axis (may be a slice)
    x: jax.Array,  # (B, S, D) compute dtype
    base_key: Optional[jax.Array] = None,
    deterministic: bool = True,
    layer_offset: int = 0,
    qk_tables: Optional[Dict] = None,
) -> jax.Array:
    """Scan the given stacked blocks over x.

    ``layer_offset`` keeps per-layer dropout keys globally consistent when the
    stack is a pipeline stage's slice: layer i's key is fold_in(base_key,
    layer_offset + i) regardless of which stage runs it.

    Returns (x, aux_sum): aux_sum accumulates MoE load-balance contributions
    over the scanned layers (0 for dense models).

    Under ``layer_types`` each layer gets its kind, statically: the unrolled
    loop by the layer's index, the scanned loop by scanning whole periods of
    the pattern (the stack viewed as (periods, period), the body a period's
    layers in a row), so the given stack starts and ends on a period.

    ``qk_tables`` are ``qk_prologue_tables``'s, made here where the caller
    has none: once for the stack, outside the loop and its remat.
    """
    c = config
    pol = normalize_remat(c.remat)
    wrapped = {}
    if qk_tables is None:
        qk_tables = qk_prologue_tables(c, x.shape[1])

    def block_of(kind):
        """``_block`` for layers of ``kind`` under the remat policy, made once."""
        if kind not in wrapped:
            wrapped[kind] = _under_remat(
                pol, lambda x, layer, key, tables: _block(
                    c, x, layer, key, deterministic, kind, tables))
        return wrapped[kind]

    def kind_at(i):  # of the i-th layer of the given stack
        return None if c.layer_types is None else c.layer_types[layer_offset + i]

    # Inside a partially-manual shard_map (the pipeline), x is varying over
    # the manual axes; the scalar aux carry must match that type or the scan
    # rejects the carry (invariant in, varying out after the first MoE add).
    def _aux0():
        from ..utils.vma import pcast_like

        return pcast_like(jnp.zeros(c.aux_shape, jnp.float32), x)

    n_local = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    if not c.scan_layers:
        aux = _aux0()
        live = base_key is not None and not deterministic
        for i in range(n_local):
            layer = jax.tree_util.tree_map(lambda t: t[i], blocks)
            ki = (
                jax.random.fold_in(base_key, layer_offset + i) if live else None
            )
            x, a = block_of(kind_at(i))(x, _constrain_layer(c, layer), ki, qk_tables)
            aux = aux + a
        return x, aux

    def _pin_carry(x):
        # Scan-carry placement (round 15): pinning the residual stream at
        # the body boundary pins the backward's stacked activation-stash
        # layout with it — without this XLA picks a stash layout of its own
        # and reconciles per iteration with collective-permute chains (the
        # banked llama-fsdp-dp4-tp2-scan reshard residue).
        if c.scan_carry_spec is None:
            return x
        return lax.with_sharding_constraint(x, c.scan_carry_spec)

    period = c.layer_period
    if period > 1:
        if n_local % period or layer_offset % period:
            raise ValueError(
                f"the scanned layer loop scans whole periods of layer_types ({period} "
                f"layers); got {n_local} layers from layer {layer_offset}"
            )
        # (periods, period, ...): one scan step runs a period's layers in a row
        blocks = jax.tree_util.tree_map(
            lambda t: t.reshape(n_local // period, period, *t.shape[1:]), blocks)
    live = base_key is not None and not deterministic

    def scan_body(carry, li):
        x, aux = carry
        layers, idx = li if live else (li, None)
        for j in range(period):
            layer = layers if period == 1 else jax.tree_util.tree_map(lambda t: t[j], layers)
            key = jax.random.fold_in(base_key, idx[j]) if live else None
            x, a = block_of(kind_at(j))(
                _pin_carry(x), _constrain_layer(c, layer), key, qk_tables)
            aux = aux + a
        return (x, aux), None

    # the layers' global indices key their dropout: scanned beside them when it is on
    idxs = (jnp.arange(n_local) + layer_offset).reshape(n_local // period, period)
    (x, aux), _ = lax.scan(scan_body, (x, _aux0()), (blocks, idxs) if live else blocks)
    return x, aux


def remat_kept_names() -> Tuple[str, ...]:
    """The ``checkpoint_name``s ``dots`` and ``full_keep_kernels`` keep through
    remat: one list for every layer kind (``_under_remat`` has the rule)."""
    from .moe import MOE_RESIDUAL_NAMES

    return (*mixers.RESIDUAL_NAMES, *MOE_RESIDUAL_NAMES, *MATMUL_CAST_NAMES)


def _under_remat(pol: str, block):
    """``block`` under the layer loop's remat policy ``pol`` (normalized).

    ``full`` keeps nothing and ``none`` everything. The two between keep **the
    values that are dear to make again and cheap to hold**, by name
    (``remat_kept_names``: one list, whatever the layer's kind; a name a block
    does not produce costs nothing): ``full_keep_kernels`` those alone, ``dots``
    those beside every matmul result (a ``dot_general`` without batch dims), so
    that its backward recomputes only the cheap elementwise chains. The list:
    each mixer's forward kernel's results (its module's ``RESIDUAL_NAMES``: the
    flash kernel's output and row sums, a ``kda`` or ``ssd`` layer's output and
    chunk states), the routed experts' gate+up (or up) grouped matmul's result,
    the router's ``HIGHEST``-precision logits, its choice and the plan that
    moves rows (``moe.MOE_RESIDUAL_NAMES``) and, after their casts, the wide
    products of ``MATMUL_CAST_NAMES``, which ``dots`` holds as their
    ``dot_general``'s results already and leaves out (with the names it would
    trade each product for its cast, and no second run would go).

    **The rule for the list has two clauses** (``tests/test_remat_flash.py``
    holds the first; PERF.md, PRs 50, 52 and 55, has the readings by cell).
    (1) A value is named only if, in the benchmark cell where it is largest, its
    second run costs at least 5 ms a step per GB it holds. (2) With it that cell
    keeps at least 1.5 GB of HBM free: the allocator's limit (16.909 GB on a
    v5e) less the peak of the step compiled under the cell's own policy
    (``hbm_headroom_gb``; ``perfbench/tools/describe_cell.py`` reads the same
    peak without a chip). Nothing else belongs to the rule. A name goes to the
    value in its compute-dtype or integer form, never to the float32 in front
    of a cast."""
    if pol == "none":
        return block
    if pol == "full":
        return jax.checkpoint(block)
    policies = jax.checkpoint_policies
    names = remat_kept_names()
    if pol == "full_keep_kernels":
        return jax.checkpoint(block, policy=policies.save_only_these_names(*names))
    return jax.checkpoint(  # dots
        block,
        policy=policies.save_from_both_policies(
            policies.dots_with_no_batch_dims_saveable,
            policies.save_only_these_names(*(n for n in names if n not in MATMUL_CAST_NAMES))))


def embed_param_names(config: TinyGPTConfig) -> Tuple[str, ...]:
    """Top-level leaves embed() reads — the pipeline schedules replicate
    exactly these across stages (wpe only exists for learned positions)."""
    return ("wte", "wpe") if config.pos_embed == "learned" else ("wte",)


def head_param_names(config: TinyGPTConfig) -> Tuple[str, ...]:
    """Top-level leaves head() reads (lnf_bias only for layernorm; the head
    matrix is wte when tied, lm_head when untied)."""
    names = ["lnf_scale"]
    if config.norm == "layernorm":
        names.append("lnf_bias")
    names.append("wte" if config.tie_embeddings else "lm_head")
    return tuple(names)


@jax.named_scope(scopes.HEAD)
def head(config: TinyGPTConfig, params: Params, x: jax.Array) -> jax.Array:
    """Final norm + LM head -> fp32 logits (B, S, V).

    The head matrix is ``wte`` when weight-tied (reference
    train_harness.py:61-62) or the separate ``lm_head`` leaf when untied
    (the Llama family) — same (V, D) layout and vocab-sharding either way.
    """
    x = _norm(config, x, params["lnf_scale"], params.get("lnf_bias"))
    w = params["wte"] if config.tie_embeddings else params["lm_head"]
    return jnp.einsum(
        "bsd,vd->bsv",
        x,
        w.astype(config.compute_dtype),
        preferred_element_type=jnp.float32,
    )


def forward(
    config: TinyGPTConfig,
    params: Params,
    idx: jax.Array,  # (B, S) int32 token ids
    targets: Optional[jax.Array] = None,  # (B, S) int32, -1 = ignore
    *,
    dropout_key: Optional[jax.Array] = None,
    deterministic: bool = True,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Forward pass -> (logits fp32 (B,S,V), loss fp32 scalar or None).

    Structure parity: reference ``TinyGPT.forward`` (train_harness.py:80-105):
    tok_emb + pos_emb -> dropout -> blocks -> ln_f -> tied lm_head ->
    cross-entropy(ignore_index=-1). The layer loop is a ``lax.scan`` over
    stacked weights (single compiled block body; optional per-layer remat);
    the embed/apply_blocks/head pieces are reused by the pipeline-parallel
    schedule (parallel.pipeline), which runs them stage-by-stage.
    """
    logits, loss, _ = _forward(config, params, idx, targets, dropout_key, deterministic)
    return logits, loss


def apply_layers(
    config: TinyGPTConfig,
    params: Params,
    x: jax.Array,
    base_key: Optional[jax.Array] = None,
    deterministic: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """The whole depth: the leading dense stack where the config has one, then
    'blocks', each through ``apply_blocks`` (the same loop, remat policy and
    per-layer placement hooks) -> (x, aux_sum)."""
    c = config
    tables = qk_prologue_tables(c, x.shape[1])  # one set for both stacks
    if c.stacks_unequal:
        return _apply_stacks(c, params, x, base_key, deterministic, tables)
    if not c.first_k_dense:
        return apply_blocks(c, params["blocks"], x, base_key, deterministic, qk_tables=tables)
    x, aux_dense = apply_blocks(
        c, params["dense_blocks"], x, base_key, deterministic, qk_tables=tables)
    x, aux = apply_blocks(
        c, params["blocks"], x, base_key, deterministic, layer_offset=c.first_k_dense,
        qk_tables=tables,
    )
    return x, aux_dense + aux


def layer_weights(config: TinyGPTConfig, params: Params, i: int) -> Params:
    """Layer ``i``'s slice of its stack (``TinyGPTConfig.layer_groups``)."""
    for name, layers in config.layer_groups:
        if i in layers:
            at = layers.index(i)
            return jax.tree_util.tree_map(lambda t: t[at], params[name])
    raise IndexError(f"no layer {i} among {config.n_layer}")


def apply_layer(config: TinyGPTConfig, layer: Params, x: jax.Array, kind: Optional[str],
                key: Optional[jax.Array] = None, deterministic: bool = True,
                qk_tables: Optional[Dict] = None) -> Tuple[jax.Array, jax.Array]:
    """One layer of ``kind`` as the unrolled loop over unequal stacks runs it:
    ``_block``'s two halves, **each under the config's remat policy on its
    own** (the backward then holds one sublayer's recomputed activations at a
    time, for one more (B, S, D) kept a layer; both halves keep the one list
    of names, ``remat_kept_names``), with the per-layer
    placement hooks, on the layer's own slice of its stack -> (x, aux). Also
    what a check calls to run one layer of the timed config alone."""
    c, pol = config, normalize_remat(config.remat)
    keys = jax.random.split(key, 2) if key is not None else (None, None)
    layer = _constrain_layer(c, layer)
    mixer = _under_remat(pol, lambda x, layer, key, tables: _mixer_half(
        c, x, layer, key, deterministic, kind, tables))
    mlp = _under_remat(pol, lambda x, layer, key: _mlp_half(c, x, layer, key, deterministic))
    has_mixer, has_mlp = c.halves(kind)  # under block_halves a block is one of the two
    if has_mixer:
        x = mixer(x, layer, keys[0], qk_tables)
    if not has_mlp:
        return x, jnp.zeros(c.aux_shape, jnp.float32)
    return mlp(x, layer, keys[1])


def _apply_stacks(c, params, x, base_key, deterministic, qk_tables):
    """The whole depth of a config whose stacks have unequal leaves (``kda``,
    ``conv`` or ``ssd`` layers beside others, attention kinds of unequal head counts,
    blocks that are one half alone): unrolled, the layers in the published
    order, each from its own stack (``apply_layer``) -> (x, aux_sum)."""
    live = base_key is not None and not deterministic
    aux = jnp.zeros(c.aux_shape, jnp.float32)
    for i, kind in enumerate(c.layer_types):
        key = jax.random.fold_in(base_key, i) if live else None
        x, a = apply_layer(c, layer_weights(c, params, i), x, kind, key, deterministic, qk_tables)
        aux = aux + a
    return x, aux


def _forward(c, params, idx, targets, dropout_key, deterministic):
    """-> (logits, loss or None, the float32 vector of ``c.step_report`` or
    None). Under ``block_diffusion`` the logits are the noisy copy's, (B, L,
    V), and the key is needed whatever ``deterministic`` says of dropout."""
    S = idx.shape[1]
    if S > c.block_size:
        raise ValueError(f"Sequence {S} exceeds block size {c.block_size}")
    report = []
    diffusion = c.block_diffusion is not None
    if diffusion:
        if dropout_key is None:
            raise ValueError("block diffusion draws its noise from forward's dropout_key")
        idx, weights, masked = bd_stream(c, idx, dropout_key)
        dropout_key = _noise_and_dropout_keys(dropout_key)[1]
    if dropout_key is not None and not deterministic:
        emb_key, scan_key = jax.random.split(dropout_key)
    else:
        emb_key = scan_key = None
    x = embed(c, params, idx, emb_key, deterministic)
    x, aux = apply_layers(c, params, x, scan_key, deterministic)
    if diffusion:
        x = x[:, :S]  # only the noisy copy goes through the head
    logits = head(c, params, x)

    if c.reports_held_overflow:
        aux, held = aux[0], aux[1:]
        report.append(held)
    loss = None
    if targets is not None:
        if diffusion:
            loss = _weighted_cross_entropy(logits, targets, weights)
        else:
            loss = _cross_entropy(logits, targets)
        if c.n_experts > 0:
            # Mean aux per routed layer: the load-balance term (and, dropless,
            # the z-loss riding it in units of router_aux_coef).
            loss = loss + c.router_aux_coef * aux / c.n_moe_layers
    if diffusion:
        report.append(jnp.sum(masked, dtype=jnp.float32)[None])
    return logits, loss, jnp.concatenate(report) if report else None


def moe_overflow_fraction(
    config: TinyGPTConfig, params: Params, idx: jax.Array
) -> jax.Array:
    """Diagnostic: mean fraction of (token, choice) expert assignments
    dropped by the capacity limit, averaged over layers, on one batch.

    Powers the published MoE row's ``expert_overflow_pct`` (the analogue
    of DeepSpeed's dropped-token logging; the reference has no MoE at
    all). Runs a dropout-free forward with the aux channel switched to
    overflow accounting (``moe_aux_mode='overflow'``) — zero impact on the
    training step itself.
    """
    c = dataclasses.replace(config, moe_aux_mode="overflow", dropout=0.0)
    x = embed(c, params, idx, None, True)
    _, aux = apply_layers(c, params, x, None, True)
    return aux / c.n_moe_layers


def moe_expert_counts(
    config: TinyGPTConfig, params: Params, idx: jax.Array
) -> jax.Array:
    """Diagnostic: (routed layers, n_experts) int32, how many of one batch's
    N x expert_top_k assignments chose each expert at each layer's router, at
    the given weights, on a dropout-free forward. Max over mean of a row is
    the routing's imbalance; under dropless routing every assignment is
    computed, so a row sums to N x expert_top_k and what is missing from that
    sum was dropped."""
    return moe_routing_rows(config, params, idx)[0]


def moe_held_rows(
    config: TinyGPTConfig, params: Params, idx: jax.Array
) -> jax.Array:
    """Diagnostic beside ``moe_expert_counts`` for a config with
    ``experts_held``: (routed layers, 2) int32, the rows the program's own
    dispatch put into the held experts' buffer at each layer and the held
    assignments that did not fit it (``moe._held_plan``'s own numbers). The
    first column equals the router's count for the held experts when the
    second is 0."""
    return moe_routing_rows(config, params, idx)[1]


def moe_routing_rows(config: TinyGPTConfig, params: Params, idx: jax.Array):
    """Both diagnostics from one walk over the layers: (``moe_expert_counts``,
    ``moe_held_rows`` or None without ``experts_held``)."""
    from .moe import routing_rows

    found = _walk_routers(config, params, idx, routing_rows)
    counts, held = zip(*found)
    return jnp.stack(counts), (None if config.experts_held is None else jnp.stack(held))


def _walk_routers(config: TinyGPTConfig, params: Params, idx: jax.Array, read):
    """``read(config, layer, the routed MLP's normed input)`` at every routed
    layer of a dropout-free forward over ``idx`` (under block diffusion: the
    stream)."""
    c = dataclasses.replace(config, dropout=0.0)
    x = embed(c, params, idx, None, True)
    found = []
    for i in range(c.n_layer):
        layer = layer_weights(c, params, i)
        kind = None if c.layer_types is None else c.layer_types[i]
        has_mixer, has_mlp = c.halves(kind)
        if has_mixer:
            x = _mixer_half(c, x, layer, None, True, kind, None)
        if not has_mlp:
            continue
        if "router" in layer:
            found.append(read(c, layer, _norm(c, x, layer["ln2_scale"], layer.get("ln2_bias"))))
        x, _ = _mlp_sublayer(c, x, layer, None, True)
    return found



def _token_nll(logits: jax.Array, targets: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(nll (N,) float32, 0 where target == -1; valid (N,)) over the flattened
    positions."""
    V = logits.shape[-1]
    logits = logits.reshape(-1, V).astype(jnp.float32)
    targets = targets.reshape(-1)
    valid = targets != -1
    safe = jnp.where(valid, targets, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    return jnp.where(valid, logz - gold, 0.0), valid


@jax.named_scope(scopes.LOSS)
def _cross_entropy_parts(
    logits: jax.Array, targets: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """(nll_sum, valid_count) over positions where target != -1 — the
    unreduced halves of the mean CE, so sequence-parallel callers can psum
    both across shards before dividing."""
    nll, valid = _token_nll(logits, targets)
    return nll.sum(), valid.sum()


@jax.named_scope(scopes.LOSS)
def _weighted_cross_entropy(
    logits: jax.Array, targets: jax.Array, weights: jax.Array
) -> jax.Array:
    """sum of weights x CE over all positions (weight 0: not counted; target
    -1: ignored), over the number of positions: block diffusion's (1 / L) sum
    over the masked tokens of CE / t, averaged over the batch's documents."""
    nll, _ = _token_nll(logits, targets)
    return jnp.sum(nll * weights.reshape(-1)) / weights.size


def _cross_entropy(
    logits: jax.Array, targets: jax.Array, seq_axis: Optional[str] = None
) -> jax.Array:
    """Mean CE over positions where target != -1 (parity: ignore_index=-1,
    reference train_harness.py:98-103). ``seq_axis`` names a manual mesh axis
    the positions are sharded over (the sequence-parallel pipeline): sums and
    counts combine across shards before the divide."""
    nll_sum, count = _cross_entropy_parts(logits, targets)
    if seq_axis is not None:
        nll_sum = lax.psum(nll_sum, seq_axis)
        count = lax.psum(count, seq_axis)
    with jax.named_scope(scopes.LOSS):
        return nll_sum / jnp.maximum(count, 1)


def loss_fn(
    config: TinyGPTConfig,
    params: Params,
    batch: jax.Array,
    targets: jax.Array,
    dropout_key: Optional[jax.Array] = None,
    deterministic: bool = True,
) -> jax.Array:
    """Scalar training loss (the differentiated function in the train step)."""
    _, loss = forward(
        config, params, batch, targets, dropout_key=dropout_key, deterministic=deterministic
    )
    return loss


def loss_and_report_fn(
    config: TinyGPTConfig,
    params: Params,
    batch: jax.Array,
    targets: jax.Array,
    dropout_key: Optional[jax.Array] = None,
    deterministic: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """``loss_fn`` for a config that reports (``step_report``): (loss, float32
    vector: the rows the held experts' buffers took and the held assignments
    that did not fit, summed over layers; the masked tokens), for
    ``jax.value_and_grad(has_aux=True)``."""
    _, loss, report = _forward(config, params, batch, targets, dropout_key, deterministic)
    return loss, report
