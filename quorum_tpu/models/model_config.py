"""ModelSpec: one dataclass describes every supported decoder-only family.

Presets cover the models named in BASELINE.json's configs and the
benchmark's. A spec is uniform over its depth (one attention kind, one MLP
kind, one window) unless it names a ``layer_pattern``: then each layer has
its own attention kind, the leading layers a dense MLP and the rest experts,
and a chip may hold a share of each layer's experts. Architecture
hyperparameters match the public model cards; weights are randomly
initialized unless a local checkpoint is provided (see
quorum_tpu.models.hf_loader) — the framework's job is serving mechanics and
performance, which depend on architecture, not on particular weight values.

``tpu://<model-id>?key=value&...`` URLs resolve through :func:`resolve_spec`:
the model id picks a preset and query parameters override any field, so tests
and operators can scale any family down (e.g. ``tpu://llama-tiny?n_layers=2``).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import NamedTuple


class Latent(NamedTuple):
    """One layer kind's latent attention: heads, the two latents' ranks, a
    head's unrotated, rotated and value sizes, the rotary base."""

    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    theta: float


@dataclass(frozen=True)
class ModelSpec:
    family: str = "llama"          # "gpt2" | "llama" | "mixtral" | "gemma" | "exaone_moe" | "dots3_note" | "falcon_h1" | "lfm2_moe"
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    d_ff: int = 14336
    max_seq: int = 4096
    sliding_window: int = 0        # >0: attend only the last W positions (mistral)
    # A layer pattern ("" = every layer alike, which is every spec above the
    # patterned families): one letter per layer, repeated over the depth
    # (or written out for every layer, where the model's list is no repeated
    # period), "L" a sliding-window layer (the last ``sliding_window``
    # positions, kept in a ring of ``ring`` positions per row), "G" a
    # full-attention layer (every position) and "C" a gated short
    # convolution (models/shortconv.py; family "lfm2_moe"): no attention,
    # no positions, a depthwise causal convolution of ``conv_taps`` taps
    # between two elementwise gates, whose cache is the row's last
    # ``conv_taps - 1`` inputs whatever its length. A patterned spec runs
    # models/patterned.py: per-layer weights, a cache per layer kind, one
    # depth loop and one expert layer for its two families, whose
    # attention conventions are written in and not chosen by the spec: K/V
    # heads, RMSNorm over each q and k head and no rotary embedding on full
    # layers unless ``rope_full``; or, with ``kv_lora_rank`` set, latent
    # attention
    # (models/latent.py). ``post_norm``: a patterned spec's blocks normalise
    # what a sub-layer gives before the add (``x + norm(f(x))``, exaone_moe)
    # and not what it takes (``x + f(norm(x))``).
    layer_pattern: str = ""
    post_norm: bool = False
    rope_full: bool = False
    conv_taps: int = 3
    # Latent attention (``kv_lora_rank`` > 0; family "dots3_note"): a query
    # latent of ``q_lora_rank`` and a key/value latent of ``kv_lora_rank``
    # per position, both normalised; ``n_heads`` heads of ``qk_nope_head_dim``
    # from the latents plus ``qk_rope_head_dim`` rotated dims (one rotated
    # key for all heads), values of ``v_head_dim``; a sigmoid gate per head
    # on the attention output. The cache keeps the latent and the rotated
    # key, no K or V. A window layer has a geometry of its own (``swa_*``,
    # with its own ``swa_rope_theta``). A full layer also scores every
    # earlier position with ``index_n_heads`` light heads of
    # ``index_head_dim`` against a cached index key and attends only the
    # ``index_topk`` positions of largest score (all of them while there
    # are no more).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    swa_n_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 10000.0
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    norm: str = "rmsnorm"          # "rmsnorm" | "layernorm"
    norm_eps: float = 1e-5
    norm_offset: float = 0.0       # weight used as (offset + w); gemma: 1.0
    pos: str = "rope"              # "rope" | "learned"
    rope_theta: float = 10000.0
    # Llama-3.1-style RoPE frequency scaling ("" = off, "llama3" = the
    # wavelength-banded interpolation the 3.1/3.2 checkpoints ship):
    # frequencies whose wavelength exceeds original_max/low_freq_factor
    # divide by `factor`, those under original_max/high_freq_factor keep
    # their value, the band between interpolates smoothly — long-context
    # extension without retraining (ops/rotary.py:scaled_rope_inv_freq).
    rope_scaling: str = ""
    rope_scaling_factor: float = 8.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_seq: int = 8192
    act: str = "swiglu"            # "swiglu" | "gelu" | "geglu" (gemma)
    emb_scale: float = 1.0         # embedding multiplier; gemma: sqrt(d_model)
    use_bias: bool = False         # attention/MLP biases (gpt2, qwen2-qkv)
    tied_lm_head: bool = True
    n_experts: int = 0             # 0 = dense
    experts_per_token: int = 2
    # Grouped sparse-MoE expert capacity = cf·k·N/E tokens (see
    # transformer._moe_mlp_grouped); ≥ E/k means no pick can ever drop.
    moe_capacity_factor: float = 2.0
    # Patterned family's expert layers: the first ``first_dense`` layers
    # keep a dense MLP of d_ff, the rest route over ``n_experts`` of width
    # ``d_ff_expert`` beside ``n_shared_experts`` always-on experts of that
    # width. The router scores every expert on its own by a sigmoid, picks
    # the top k by score + ``router_bias`` and weighs the picks
    # ``router_scale * s_i / sum_picked s``. ``experts_held`` > 0: this chip
    # holds experts [expert_first, expert_first + experts_held) of each
    # expert layer, which further chips share; the router still scores all
    # n_experts and what the absent experts would add is left out.
    first_dense: int = 0
    d_ff_expert: int = 0
    n_shared_experts: int = 0
    router_scale: float = 1.0
    experts_held: int = 0
    expert_first: int = 0
    # Seeded init of the patterned families: what a sub-layer adds to the
    # stream is scaled by 1/sqrt(2 * init_depth), the published depth,
    # whatever n_layers is cut to; the router's selection bias is
    # ``init_bias_dev`` times a normal (models/init.py).
    init_depth: int = 0
    init_bias_dev: float = 0.05
    # A Mamba-2 mixer beside attention in every block (``ssm_heads`` > 0;
    # family "falcon_h1"; models/ssm.py): both read the block's normed input
    # and both add to the stream. ``ssm_heads`` heads of ``ssm_head_dim``
    # channels, each with a recurrent state of ``ssm_head_dim`` x
    # ``ssm_state``; the state's input and output vectors (B, C) are shared
    # by the heads of one of ``ssm_groups`` groups; a depthwise causal
    # convolution of ``ssm_conv`` taps before the recurrence; prefill runs
    # the recurrence in chunks of ``ssm_chunk`` positions. The cache keeps,
    # per row and layer, the float32 state and the convolution's last
    # ``ssm_conv - 1`` inputs beside K and V (``StateKV``).
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # muP multipliers (1.0 = none, and then no operation): on attention's
    # input and output, on the keys after their projection, on the mixer's
    # input and output and on the five parts of its input projection (gate
    # z, x, B, C, dt), on the MLP's gate product and its down product, on
    # the logits. The embedding's is ``emb_scale``.
    attn_in_mult: float = 1.0
    attn_out_mult: float = 1.0
    key_mult: float = 1.0
    ssm_in_mult: float = 1.0
    ssm_out_mult: float = 1.0
    ssm_mults: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_gate_mult: float = 1.0
    mlp_down_mult: float = 1.0
    lm_head_mult: float = 1.0
    dtype: str = "bfloat16"

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def held(self) -> int:
        """Experts whose weights are here."""
        return self.experts_held or self.n_experts

    @property
    def row_state(self) -> bool:
        """Whether a row holds a state beside (or in place of) its cached
        positions: a mixer's recurrence and convolution tail, a short
        convolution's tail. Such a row has no prefix to take up again and
        cannot be taken back a position; the engine's rules for it
        (engine/engine.py) read this and nothing else."""
        return bool(self.ssm_heads
                    or self.layer_pattern and self.layers_of("C"))

    @property
    def kv_positions_minor(self) -> bool:
        """Whether a patterned spec's full layers keep K and V ``[slots, K,
        head_dim, max_seq]``, the positions in the chip's 128 lanes, and not
        ``[slots, K, max_seq, head_dim]``: where the heads are narrower than
        the lanes (lfm2's 64). With such a head in the lanes the v5e
        compiler keeps a side positions-minor for attention and head-minor
        for the decode step's write loop, and copies every full side twice
        a step (PERF.md section 6, PR 52)."""
        return bool(self.layer_pattern and not self.kv_lora_rank
                    and self.head_dim < 128)

    @property
    def ring(self) -> int:
        """Positions a window layer keeps per row: the power of two at or
        above ``sliding_window``."""
        return 1 << max(self.sliding_window - 1, 0).bit_length()

    @property
    def ssm_width(self) -> int:
        """The mixer's channels: ``ssm_heads`` x ``ssm_head_dim``."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """Channels the mixer's convolution runs over: x, B and C."""
        return self.ssm_width + 2 * self.ssm_groups * self.ssm_state

    def latent(self, kind: str) -> "Latent":
        """The latent attention's sizes in a layer of ``kind``."""
        if kind == "L":
            return Latent(self.swa_n_heads, self.swa_q_lora_rank,
                          self.swa_kv_lora_rank, self.swa_qk_nope_head_dim,
                          self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                          self.swa_rope_theta)
        return Latent(self.n_heads, self.q_lora_rank, self.kv_lora_rank,
                      self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim, self.rope_theta)

    def attn_kind(self, layer: int) -> str:
        return self.layer_pattern[layer % len(self.layer_pattern)]

    def layers_of(self, kind: str) -> list[int]:
        return [i for i in range(self.n_layers) if self.attn_kind(i) == kind]

    @property
    def periods(self) -> tuple[int, int, int]:
        """``(start, length, count)``: the layers from ``first_dense`` on as
        ``count`` >= 2 whole repeats of their first ``length`` kinds (the
        shortest such), where those are full-attention and short-convolution
        layers only; ``(n_layers, 0, 0)`` where they are not. Such layers
        are held stacked, a period's slot a leaf ``[count, ...]``, and run
        as one ``lax.scan`` over the periods (models/patterned.py): a third
        of the program to compile at three periods. A window layer's ring
        and a latent layer's rows are not addressed by period yet."""
        start, n = self.first_dense, self.n_layers
        kinds = [self.attn_kind(i) for i in range(start, n)] \
            if self.layer_pattern and not self.kv_lora_rank else []
        if set(kinds) <= {"G", "C"}:
            for length in range(1, len(kinds) // 2 + 1):
                if len(kinds) % length == 0 and kinds == kinds[:length] * (
                        len(kinds) // length):
                    return start, length, len(kinds) // length
        return n, 0, 0

    @property
    def gated_mlp(self) -> bool:
        return self.act in ("swiglu", "geglu")

    def validate(self) -> "ModelSpec":
        assert self.n_heads % self.n_kv_heads == 0, "n_heads must divide by n_kv_heads"
        assert self.head_dim % 2 == 0, "RoPE needs even head_dim"
        assert self.act in ("swiglu", "gelu", "geglu")
        assert self.norm in ("rmsnorm", "layernorm")
        assert self.pos in ("rope", "learned")
        assert self.rope_scaling in ("", "llama3"), (
            f"unsupported rope_scaling {self.rope_scaling!r}")
        if self.ssm_heads:
            assert not self.layer_pattern and not self.is_moe, (
                "a mixer beside attention is the dense family's")
            assert min(self.ssm_head_dim, self.ssm_state, self.ssm_conv - 1,
                       self.ssm_chunk) > 0
            assert self.ssm_heads % self.ssm_groups == 0
            assert len(self.ssm_mults) == 5
            assert self.pos == "rope" and self.norm == "rmsnorm"
            assert self.gated_mlp and not self.use_bias
        if self.layer_pattern:
            assert set(self.layer_pattern) <= {"L", "G", "C"}, (
                f"layer_pattern {self.layer_pattern!r}: L (window), G "
                "(full) and C (short convolution) only")
            if "C" in self.layer_pattern:
                assert self.conv_taps > 1 and not self.kv_lora_rank
            assert self.sliding_window > 0 or "L" not in self.layer_pattern, (
                "a window layer needs sliding_window > 0")
            assert self.ring <= self.max_seq
            assert self.pos == "rope" and self.norm == "rmsnorm"
            assert self.gated_mlp and not self.use_bias
            assert 0 <= self.first_dense <= self.n_layers
            if self.kv_lora_rank:
                for kind in set(self.layer_pattern[:self.n_layers]):
                    g = self.latent(kind)
                    assert min(g) > 0 and g.rope % 2 == 0, (kind, g)
                assert self.index_topk > 0 and self.index_n_heads > 0
                assert self.qk_rope_head_dim <= self.index_head_dim
            if self.first_dense < self.n_layers:
                assert self.n_experts > 0 and self.d_ff_expert > 0
                assert self.experts_per_token <= self.n_experts
                assert self.expert_first + self.held <= self.n_experts, (
                    f"experts {self.expert_first}..{self.expert_first + self.held}"
                    f" of {self.n_experts}")
        return self


def _gpt2(**kw) -> ModelSpec:
    base = dict(
        family="gpt2", vocab_size=50257, d_model=768, n_layers=12, n_heads=12,
        n_kv_heads=12, head_dim=64, d_ff=3072, max_seq=1024, norm="layernorm",
        pos="learned", act="gelu", use_bias=True, tied_lm_head=True,
    )
    base.update(kw)
    return ModelSpec(**base)


MODEL_PRESETS: dict[str, ModelSpec] = {
    # BASELINE.json config[0]: GPT-2-124M CPU-runnable reference model
    "gpt2": _gpt2(),
    "gpt2-medium": _gpt2(d_model=1024, n_layers=24, n_heads=16, n_kv_heads=16, d_ff=4096),
    # BASELINE.json configs 2-3: 7-8B dense models
    "llama-3-8b": ModelSpec(
        family="llama", vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, head_dim=128, d_ff=14336, max_seq=8192, rope_theta=500000.0,
        tied_lm_head=False,
    ),
    # Llama-3.1-8B: identical transformer to llama-3-8b plus the llama3
    # RoPE frequency scaling (factor 8 over the 8192-token original
    # context — the published 3.1 long-context recipe; formula pinned
    # bit-for-bit against transformers in tests/test_hf_loader.py).
    # max_seq defaults to 16384 (the cache window actually allocated);
    # raise via ?max_seq= up to the 131072 the scaling supports.
    "llama-3.1-8b": ModelSpec(
        family="llama", vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, head_dim=128, d_ff=14336, max_seq=16384, rope_theta=500000.0,
        tied_lm_head=False, rope_scaling="llama3", rope_scaling_factor=8.0,
        rope_low_freq_factor=1.0, rope_high_freq_factor=4.0,
        rope_original_max_seq=8192,
    ),
    # Llama-3.2-1B: the small 3.2 config (16 layers, GQA 32q/8kv, tied
    # head, llama3 scaling factor 32).
    "llama-3.2-1b": ModelSpec(
        family="llama", vocab_size=128256, d_model=2048, n_layers=16, n_heads=32,
        n_kv_heads=8, head_dim=64, d_ff=8192, max_seq=16384, rope_theta=500000.0,
        tied_lm_head=True, rope_scaling="llama3", rope_scaling_factor=32.0,
        rope_low_freq_factor=1.0, rope_high_freq_factor=4.0,
        rope_original_max_seq=8192,
    ),
    "mistral-7b": ModelSpec(
        family="llama", vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, head_dim=128, d_ff=14336, max_seq=8192, rope_theta=1000000.0,
        sliding_window=4096,
        tied_lm_head=False,
    ),
    # Gemma-7B: GeGLU MLP, (1 + w) RMSNorm, sqrt(d_model)-scaled embeddings,
    # tied head (google/gemma-7b config.json / transformers GemmaConfig).
    "gemma-7b": ModelSpec(
        family="gemma", vocab_size=256000, d_model=3072, n_layers=28, n_heads=16,
        n_kv_heads=16, head_dim=256, d_ff=24576, max_seq=8192, act="geglu",
        norm_offset=1.0, norm_eps=1e-6, emb_scale=3072.0 ** 0.5,
        tied_lm_head=True,
    ),
    # BASELINE.json config[3]: DeepSeek-R1-Distill-Qwen-7B (qwen2 arch, qkv bias)
    "deepseek-r1-distill-7b": ModelSpec(
        family="llama", vocab_size=152064, d_model=3584, n_layers=28, n_heads=28,
        n_kv_heads=4, head_dim=128, d_ff=18944, max_seq=8192, rope_theta=10000.0,
        use_bias=True, tied_lm_head=False,
    ),
    # Qwen2.5-7B: same qwen2 architecture (qkv bias), θ=1e6
    "qwen2.5-7b": ModelSpec(
        family="llama", vocab_size=152064, d_model=3584, n_layers=28, n_heads=28,
        n_kv_heads=4, head_dim=128, d_ff=18944, max_seq=8192, rope_theta=1000000.0,
        use_bias=True, tied_lm_head=False,
    ),
    # BASELINE.json config[4]: Mixtral-8x7B MoE
    "mixtral-8x7b": ModelSpec(
        family="mixtral", vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, head_dim=128, d_ff=14336, max_seq=8192, rope_theta=1000000.0,
        n_experts=8, experts_per_token=2, tied_lm_head=False,
    ),
    # K-EXAONE-236B-A23B (LGAI-EXAONE, model_type exaone_moe): "LLLG" window
    # and full layers, a dense first layer, then 128 sigmoid-routed experts
    # (8 picked, scaled 2.5) beside one shared expert; full layers carry no
    # rotary embedding, q and k heads are normalised, blocks are post-norm
    # (the EXAONE 4.0 conventions). 471 GB in bf16: served as one chip's
    # share, ``?n_layers=8&experts_held=16&vocab_size=19200``
    # (docs/tpu_backends.md). The multi-token-prediction layer is not loaded.
    "k-exaone-236b-a23b": ModelSpec(
        family="exaone_moe", vocab_size=153600, d_model=6144, n_layers=48,
        n_heads=64, n_kv_heads=8, head_dim=128, d_ff=18432, max_seq=4096,
        rope_theta=1000000.0, sliding_window=128, layer_pattern="LLLG",
        tied_lm_head=False, n_experts=128, experts_per_token=8, first_dense=1,
        d_ff_expert=2048, n_shared_experts=1, router_scale=2.5, init_depth=48,
        post_norm=True,
    ),
    # dots3-note-prev (dots-studio, model_type dots3_note, 288B-A17B): 46
    # layers "GG" + "LLLG" x 11. Latent attention of two geometries: full
    # layers of 128 heads (latents 1024 / 512, 128 + 64 dims, values 128,
    # theta 8e7) that attend the 2,048 positions a 64-head indexer scores
    # highest; window-513 layers of 64 heads (latents 1024 / 1024, 192 + 64,
    # values 128, theta 5e4); a sigmoid gate per head; both latents rescaled
    # by sqrt(hidden / rank) after their norms; pre-norm blocks. A dense
    # first layer, then 256 sigmoid-routed experts (8 picked, scaled 1)
    # beside one shared expert. 576 GB in bf16: served as one chip's share,
    # ``?n_layers=6&experts_held=32&vocab_size=19008``
    # (docs/tpu_backends.md). The vision and audio towers and the
    # multi-token-prediction layer are not loaded.
    "dots3-note-prev": ModelSpec(
        family="dots3_note", vocab_size=152064, d_model=5120, n_layers=46,
        n_heads=128, n_kv_heads=128, head_dim=192, d_ff=13824, max_seq=16384,
        rope_theta=80000000.0, sliding_window=513,
        layer_pattern="GG" + "LLLG" * 11, tied_lm_head=False, n_experts=256,
        experts_per_token=8, first_dense=1, d_ff_expert=1536,
        n_shared_experts=1, router_scale=1.0, init_depth=46,
        q_lora_rank=1024, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, swa_n_heads=64,
        swa_q_lora_rank=1024, swa_kv_lora_rank=1024,
        swa_qk_nope_head_dim=192, swa_qk_rope_head_dim=64, swa_v_head_dim=128,
        swa_rope_theta=50000.0, index_n_heads=64, index_head_dim=128,
        index_topk=2048, init_bias_dev=0.01,
    ),
    # Falcon-H1-34B-Instruct (tiiuae, model_type falcon_h1): 72 blocks, all
    # alike, each with grouped-query attention (20 / 4 heads of 128, theta
    # 1e11) AND a Mamba-2 mixer (32 heads of 128 with a state of 256, 2
    # groups, convolution 4, chunks of 128) on the same normed input, then a
    # SwiGLU of 21504; muP multipliers throughout. 67 GB in bf16: served as
    # the first pipeline stage, ``?n_layers=6&max_seq=2048``
    # (docs/tpu_backends.md).
    "falcon-h1-34b": ModelSpec(
        family="falcon_h1", vocab_size=261120, d_model=5120, n_layers=72,
        n_heads=20, n_kv_heads=4, head_dim=128, d_ff=21504, max_seq=8192,
        rope_theta=1e11, tied_lm_head=False, norm_eps=1e-5,
        ssm_heads=32, ssm_head_dim=128, ssm_state=256, ssm_groups=2,
        ssm_conv=4, ssm_chunk=128, emb_scale=5.656854249492381,
        attn_in_mult=1.0, attn_out_mult=0.0375,
        key_mult=0.011048543456039804, ssm_in_mult=0.25,
        ssm_out_mult=0.08838834764831845,
        ssm_mults=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                   0.3535533905932738),
        mlp_gate_mult=0.1767766952966369,
        mlp_down_mult=0.011160714285714284, lm_head_mult=0.0078125,
    ),
    # LFM2-8B-A1B (LiquidAI, model_type lfm2_moe, 8.3B of which 1.5B active):
    # 24 pre-norm blocks on a stream of 2048, the published ``layer_types``
    # written out: 18 gated short convolutions (3 taps, no bias, no
    # activation) and 6 full-attention layers (32 / 8 heads of 64, q and k
    # heads normalised, then rotary, theta 1e6); two leading dense SwiGLUs of
    # 7168, then 32 sigmoid-routed experts of 1792 (4 picked, a selection
    # bias, weights normalised, no shared expert); embedding tied to the
    # head. 16.7 GB in bf16: served as the first of two pipeline stages,
    # ``?n_layers=14&max_seq=2048`` (docs/tpu_backends.md).
    "lfm2-8b-a1b": ModelSpec(
        family="lfm2_moe", vocab_size=65536, d_model=2048, n_layers=24,
        n_heads=32, n_kv_heads=8, head_dim=64, d_ff=7168, max_seq=4096,
        rope_theta=1000000.0, layer_pattern="CCGCCCGCCCGCCCGCCCGCCGCC",
        rope_full=True, conv_taps=3, tied_lm_head=True, n_experts=32,
        experts_per_token=4, first_dense=2, d_ff_expert=1792,
        n_shared_experts=0, router_scale=1.0, init_depth=24,
        post_norm=False, norm_eps=1e-5,
    ),
    # Scaled-down test/dev presets (CPU-fast, same code paths)
    "gpt2-tiny": _gpt2(vocab_size=512, d_model=64, n_layers=2, n_heads=4,
                       n_kv_heads=4, head_dim=16, d_ff=128, max_seq=128),
    "llama-tiny": ModelSpec(
        family="llama", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, max_seq=128, tied_lm_head=False,
    ),
    "mixtral-tiny": ModelSpec(
        family="mixtral", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, max_seq=128, n_experts=4,
        experts_per_token=2, tied_lm_head=False,
    ),
    # the k-exaone family at a size the CPU runs: a dense layer and two
    # "LLLG" periods, 16 experts of which 4 are held, top-4, window 8; heads
    # of the model's own 128, so that its full layers keep K and V as the
    # model's do (``kv_positions_minor``)
    "k-exaone-tiny": ModelSpec(
        family="exaone_moe", vocab_size=512, d_model=64, n_layers=8,
        n_heads=4, n_kv_heads=2, head_dim=128, d_ff=192, max_seq=128,
        sliding_window=8, layer_pattern="LLLG", tied_lm_head=False,
        n_experts=16, experts_per_token=4, first_dense=1, d_ff_expert=32,
        n_shared_experts=1, router_scale=2.5, experts_held=4, init_depth=48,
        post_norm=True,
    ),
    # the dots3_note family at a size the CPU runs: "GGLLLG", both latent
    # geometries, 16 experts of which 4 are held, top-4; the indexer keeps
    # 16 positions and the window 9 (ring 16), both far under max_seq
    "dots3-tiny": ModelSpec(
        family="dots3_note", vocab_size=512, d_model=64, n_layers=6,
        n_heads=4, n_kv_heads=4, head_dim=24, d_ff=192, max_seq=128,
        rope_theta=80000000.0, sliding_window=9,
        layer_pattern="GG" + "LLLG" * 11, tied_lm_head=False, n_experts=16,
        experts_per_token=4, first_dense=1, d_ff_expert=32,
        n_shared_experts=1, router_scale=1.0, experts_held=4, init_depth=46,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, swa_n_heads=2,
        swa_q_lora_rank=32, swa_kv_lora_rank=32, swa_qk_nope_head_dim=24,
        swa_qk_rope_head_dim=8, swa_v_head_dim=16, swa_rope_theta=50000.0,
        index_n_heads=4, index_head_dim=16, index_topk=16,
        init_bias_dev=0.01,
    ),
    # the lfm2_moe family at a size the CPU runs: two leading dense
    # convolution layers, then two periods "GCC" over expert layers, which
    # run as a scan (``?n_layers=7`` leaves no whole periods: written out);
    # 8 experts, all held, top-2
    "lfm2-moe-tiny": ModelSpec(
        family="lfm2_moe", vocab_size=512, d_model=64, n_layers=8,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, max_seq=128,
        rope_theta=1000000.0, layer_pattern="CCGCCGCC", rope_full=True,
        conv_taps=3, tied_lm_head=True, n_experts=8, experts_per_token=2,
        first_dense=2, d_ff_expert=32, n_shared_experts=0, router_scale=1.0,
        init_depth=24, post_norm=False,
    ),
    # the falcon_h1 family at a size the CPU runs: every multiplier off 1
    "falcon-h1-tiny": ModelSpec(
        family="falcon_h1", vocab_size=256, d_model=64, n_layers=3,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, max_seq=128,
        rope_theta=1e11, tied_lm_head=False, ssm_heads=4, ssm_head_dim=16,
        ssm_state=16, ssm_groups=2, ssm_conv=4, ssm_chunk=8, emb_scale=1.5,
        attn_in_mult=0.8, attn_out_mult=0.6, key_mult=0.5, ssm_in_mult=0.25,
        ssm_out_mult=0.7, ssm_mults=(0.35, 0.25, 0.18, 0.5, 0.35),
        mlp_gate_mult=0.4, mlp_down_mult=0.3, lm_head_mult=0.125,
    ),
    "gemma-tiny": ModelSpec(
        family="gemma", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, max_seq=128, act="geglu",
        norm_offset=1.0, norm_eps=1e-6, emb_scale=64.0 ** 0.5, tied_lm_head=True,
    ),
}

_FIELD_TYPES = {f.name: f.type for f in dataclasses.fields(ModelSpec)}


def resolve_spec(model_id: str, options: dict[str, str] | None = None) -> ModelSpec:
    """Preset lookup + query-string overrides (``tpu://`` URL semantics)."""
    spec = MODEL_PRESETS.get(model_id)
    if spec is None:
        raise KeyError(
            f"Unknown tpu:// model id {model_id!r}; known: {sorted(MODEL_PRESETS)}"
        )
    overrides: dict[str, object] = {}
    for k, v in (options or {}).items():
        if k not in _FIELD_TYPES:
            continue  # engine-level options (e.g. tp=, batch=) are handled upstream
        t = _FIELD_TYPES[k]
        if t in ("int", int):
            overrides[k] = int(v)
        elif t in ("float", float):
            overrides[k] = float(v)
        elif t in ("bool", bool):
            overrides[k] = v.lower() in ("1", "true", "yes")
        else:
            overrides[k] = v
    return dataclasses.replace(spec, **overrides).validate()
