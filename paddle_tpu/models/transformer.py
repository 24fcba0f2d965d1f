"""Transformer models (reference benchmark/fluid/machine_translation.py +
fluid Transformer configs; built here as the flagship TPU model).

Decoder-only LM (GPT-style) with causal masking, plus an encoder stack for
NMT. All ops are dense batched matmuls -> MXU-friendly; parameters carry
naming conventions ('*.qkv*', '*.ffn1*', ...) that parallel/api.py's sharding
rules match for tensor parallelism.
"""
import collections
import contextlib

import numpy as np

from .. import layers
# decode steps gather rows of the SAME sinusoid table the
# add_position_encoding op applies during prefill — sharing the builder
# keeps a token's embedding bit-identical on both paths (re-exported)
from ..framework import default_main_program
from ..ops import ssm_ops
from ..ops.tensor_ops import position_encoding_table  # noqa: F401
from ..param_attr import ParamAttr

__all__ = ['multi_head_attention', 'transformer_block', 'build_lm',
           'LMConfig', 'position_encoding_table', 'kv_cache_names',
           'build_lm_decode_step', 'build_lm_prefill_paged',
           'build_lm_drafter', 'build_lm_verify']


class LMConfig(object):
    """The decoder block, as fields. The defaults are the block this repo
    trains and serves everywhere (pre-LayerNorm, sinusoid positions added
    to the embedding, heads of d_model / n_head, biases, a GELU FFN of
    width d_ff). The other values are served by the decode step and the
    prefill only (build_lm_decode_step, build_lm_prefill_paged); every
    other builder refuses them by name (`_require_classic_block`):

    - ``norm='rms_norm'`` (``rms_eps``): RMSNorm, no bias, for every norm;
    - ``position='rope'`` (``rope_theta``): nothing is added to the
      embedding; q and k are rotated by the fed positions and K is cached
      rotated;
    - ``head_dim``: a head size other than d_model / n_head;
    - ``qk_norm``: the block's norm over the WHOLE projected q and k
      (before the split into heads, OLMoE's way); ``qk_norm='head'``: over
      each head's ``head_dim`` numbers alone, one weight ``[head_dim]``
      for q and one for k shared by the heads (LFM2's way);
    - ``n_kv_head``: fewer K/V heads than query heads (grouped-query
      attention): query head ``h`` reads K/V head ``h // (n_head //
      n_kv_head)``, and the pools hold ``n_kv_head`` heads;
    - ``position='none'``: nothing is added to the embedding and nothing
      is rotated (Jamba: the recurrence carries the order);
    - ``ffn='gated'``: every layer's FFN is the dense SiLU-gated
      ``(silu(x W_g) * (x W_u)) W_d`` of width ``d_ff`` (`_dense_ffn`);
    - ``layer_types``: one of ``'attention'`` | ``'conv'`` | ``'window'`` |
      ``'ssm'`` a layer. An ``'ssm'`` layer's mixer is Jamba's Mamba-1
      block (``[u | z] = h W_in``; a causal depthwise convolution of
      ``ssm_conv`` taps with a bias and a SiLU; ``dt``, ``B``, ``C`` from
      ``u`` through a projection and a norm each; the recurrence over a
      ``[ssm_state, ssm_expand x d_model]`` state; ``(y * silu(z))
      W_out``: ops/ssm_ops.py). It caches no key and no value but the
      recurrence's state and the convolution's last ``ssm_conv - 1``
      inputs, a fixed size whatever the context, in two pools of their
      own with A ROW A SLOT (`SSM_STATE`, `SSM_TAIL`), fed as 'gen_srow'. A ``'window'`` layer is an attention layer whose query at
      position ``i`` sees key ``j`` iff ``0 <= i - j < sliding_window``
      (K-EXAONE's, EXAONE 4.0's local layers). It keeps K and V pools of
      its own, under block ids of its own: a slot's FEW blocks there are a
      ring (logical block ``b`` lies in column ``b % ring`` of the slot's
      window table, `window_ring`), so a page behind the window is handed
      on and the pools do not grow with the context. With
      ``global_rope=False`` the ``'attention'`` (global) layers rotate
      nothing (NoPE) and only the window layers are rotated; with
      ``attention_rope`` (keys of `ROPE_KEYS`) they rotate by parameters
      of their own: another ``theta``, or YaRN's ``factor``,
      ``original_max_position``, ``beta_fast``, ``beta_slow`` and
      ``attention_factor`` (`layers.rotary_embedding`). A
      ``'conv'`` layer's mixer is LFM2's gated short convolution
      (``[B | C | u] = z W_in``; ``y = (C * conv(B * u)) W_out``, a
      causal depthwise convolution of ``conv_kernel`` taps, no bias); it
      caches no key and no value but the last ``conv_kernel - 1`` rows
      of ``B * u``, in a pool of its own under the K/V pools' block ids
      (ops/short_conv_ops.py). The K/V pools hold the attention layers
      only;
    - ``layer_types`` ``'ssd'``: a Mamba-2 mixer (Nemotron-H's; ops/
      ssd_ops.py): ``[z | xBC | dt] = h W_in``; the convolution over all
      of ``xBC``; ``ssm_heads`` heads of ``ssm_head_dim`` channels, each
      with a SCALAR decay and a ``[ssm_head_dim, ssm_state]`` state, ``B``
      and ``C`` shared by the heads of each of ``ssm_groups`` groups; the
      gate, then an RMSNorm a group; ``W_out``. Prompts are scanned in
      blocks of ``ssm_chunk`` rows. Its state and tail are two more pools
      a row a slot (`SSD_STATE`, `SSD_TAIL`);
    - ``layer_types`` ``'gdn'``: a Gated DeltaNet mixer (Qwen3-Next's and
      Olmo-Hybrid's linear-attention layers; ops/gdn_ops.py): ``[q | k | v
      | z] = h W_in`` and ``[b | a] = h W_ba``; a causal depthwise
      convolution of ``ssm_conv`` taps, no bias, and a SiLU over all of
      ``[q | k | v]``;
      ``gdn_key_heads`` heads of ``gdn_key_dim`` for q and k, l2-normed,
      ``gdn_value_heads`` heads of ``gdn_value_dim`` for v (value head ``h``
      reads key head ``h // (value heads / key heads)``); the delta rule
      with a scalar decay and a write strength a head over a ``[key dim,
      value dim]`` state a value head; an RMSNorm over each head's values,
      then the gate ``silu(z)``; ``W_out``. Prompts run the chunked form in
      blocks of ``gdn_chunk`` rows. Its state and tail are two more pools a
      row a slot (`GDN_STATE`, `GDN_TAIL`). ``gdn_allow_neg_eigval``: the
      write strength is ``2 sigmoid(b)``, in (0, 2), so the state's
      transition may have an eigenvalue in (-1, 0) (Olmo-Hybrid's
      ``linear_allow_neg_eigval``);
    - ``norm_placement='post'``: the block's norms sit on each sublayer's
      OUTPUT and the sublayer reads the stream un-normed, ``h = x +
      norm_1(mixer(x))``, ``y = h + norm_2(ffn(h))`` (the Olmo 2 / Olmo 3
      line's "reordered norm", arXiv:2501.00656; RMSNorm only; the final
      norm stays on the stream); the default ``'pre'`` is ``x +
      f(norm(x))``; ``'sandwich'``: a norm before AND after each sublayer,
      four weights a layer, ``h = x + norm_1o(mixer(norm_1(x)))``, ``y = h
      + norm_2o(ffn(norm_2(h)))`` (``ln1`` / ``ln1_out`` / ``ln2`` /
      ``ln2_out``; RMSNorm only);
    - ``passes``: the whole stack of ``n_layer`` layers is run that many
      times a token over ONE set of weights (a looped language model,
      arXiv:2510.25741): the final norm closes EVERY pass and its output
      is the next pass's input; after every pass the exit gate ``lambda_t
      = sigmoid(x^t w + b)`` (``exit_gate.w [d_model, 1]``, ``exit_gate.b
      [1]``) is read, and the logits come from the last pass's output. A
      query of pass ``t`` at layer ``l`` attends what pass ``t`` of layer
      ``l`` cached: the K and V pools hold ``passes x n_attn_layers``
      cache layers (`cache_ordinal`), the parameters stay ``n_layer``
      layers'. Every token runs every pass (an exit threshold of 1); the
      decode step returns, behind its tokens, the exit distribution's
      mass a pass summed over the live rows (`EXIT_MASS_ONE`). Built with
      'attention' layers, ``attention='mha'`` and a dense FFN only, by the
      two serving programs only;
    - ``attention_gate``: an attention layer's q projection is twice as
      wide -- head ``h`` owns columns ``2 h head_dim ..``: its q, then its
      gate -- and the attention's output is multiplied by ``sigmoid(gate)``
      before ``W_o`` (Qwen3-Next's full-attention layers);
    - ``rotary_dim``: the first ``rotary_dim`` numbers of each head of q
      and k alone are rotated, as a head of that size
      (``partial_rotary_factor`` x ``head_dim``); the rest pass through;
    - ``norm_zero_centred``: the block's norms, the final norm and the
      q/k-norm multiply by ``1 + w`` (the weight starts at 0), not by
      ``w``; a Gated DeltaNet layer's output norm stays ``w``;
    - ``shared_expert_gate``: the shared expert's output is multiplied by
      ``sigmoid(x w_sg)``, ``w_sg [d_model, 1]``, a scalar a row;
    - ``layer_types`` ``'ffn'``: a model that has such layers is made of
      layers of ONE sublayer, ``x + f(norm(x))`` (Nemotron-H's block): an
      ``'ffn'`` layer is the FFN alone (norm ``ln2``), a layer of any
      other kind its mixer alone (``ln1``). Without one, every layer is
      norm, mixer, norm, FFN. `has_mixer` / `has_ffn` say it a layer, and
      both serving loops ask nothing else;
    - ``tie_embeddings``: the head contracts against ``tok_emb.w`` where
      it lies; there is no ``lm_head.w``;
    - ``matmul_precision='highest'``: the two serving programs multiply
      their float32 operands as float32 (`Program.matmul_precision`: every
      matmul that states no precision of its own, the kernels' among
      them); the default None leaves the backend's, bfloat16 operands on
      the TPU;
    - ``router_eps``: what the sigmoid router adds to the sum it
      divides the chosen weights by;
    - ``bias=False``: no bias on any projection;
    - ``ffn='moe'``: a dropless top-``experts_per_token``-of-``n_experts``
      FFN of SiLU-gated experts of width ``expert_width``
      (``layers.moe_ffn``), weights renormalised over the chosen experts
      only with ``norm_topk_prob``. ``moe_score='sigmoid'``: sigmoid
      scores, a bias parameter added to them for the choice alone,
      ``routed_scale`` on the weights (DeepSeek-V3's router).
      ``n_shared_experts``: that many more experts of the same width
      every row goes through, beside the routed ones.
      ``experts_held = (first, count)``: the chip's SHARE of the experts
      under expert parallelism — the router scores all ``n_experts``, the
      layer holds and computes ``count`` of them from ``first`` on, and
      what the others would add is left out (ops/moe_ops.py).
      ``n_dense_layers``: that many leading layers take a dense
      SiLU-gated FFN instead, ``(silu(x W_g) * (x W_u)) W_d`` of width
      ``d_ff``. ``expert_form='relu2'``: an expert, routed or shared, is
      ``relu(x W_u)^2 W_d``, two matrices and no gate.
      ``shared_expert_width``: the shared experts' width together, where
      it is not ``n_shared_experts x expert_width``;
    - ``attention='mla'``: latent attention (DeepSeek-V2/V3). q through a
      rank-``q_lora_rank`` bottleneck with its norm, heads of
      ``qk_nope_dim + qk_rope_dim``; K and V through ONE normed latent of
      ``kv_lora_rank`` and ONE rotary key of ``qk_rope_dim`` shared by all
      heads, which is all that is cached (``kv_width``; no V pool); values
      of ``v_head_dim`` a head. ``rope_interleave``: rotate the pairs
      ``(2i, 2i + 1)``. The prefill attends in the expanded form, the
      decode step in the absorbed one (ops/mla_ops.py)."""

    def __init__(self, vocab_size=32000, seq_len=512, d_model=512,
                 n_head=8, n_layer=6, d_ff=2048, dropout=0.1,
                 attn_dropout=None, use_flash_attention=True,
                 norm='layer_norm', rms_eps=1e-5, position='sinusoid',
                 rope_theta=10000.0, head_dim=None, qk_norm=False,
                 bias=True, ffn='gelu', n_experts=0, experts_per_token=0,
                 expert_width=0, norm_topk_prob=False, moe_score='softmax',
                 routed_scale=1.0, n_shared_experts=0, experts_held=None,
                 n_dense_layers=0,
                 attention='mha', q_lora_rank=0, kv_lora_rank=0,
                 qk_nope_dim=0, qk_rope_dim=0, v_head_dim=0,
                 rope_interleave=False, layer_types=None, conv_kernel=3,
                 n_kv_head=None, tie_embeddings=False, router_eps=1e-20,
                 sliding_window=0, global_rope=True, ssm_expand=2,
                 ssm_state=16, ssm_conv=4, ssm_dt_rank=None, ssm_heads=0,
                 ssm_head_dim=0, ssm_groups=1, ssm_chunk=128,
                 expert_form='gated', shared_expert_width=None,
                 matmul_precision=None, attention_rope=None,
                 gdn_key_heads=0, gdn_value_heads=0, gdn_key_dim=0,
                 gdn_value_dim=0, gdn_chunk=64, attention_gate=False,
                 rotary_dim=None, norm_zero_centred=False,
                 shared_expert_gate=False, norm_placement='pre',
                 gdn_allow_neg_eigval=False, passes=1):
        self.vocab_size = vocab_size
        self.seq_len = seq_len
        self.d_model = d_model
        self.n_head = n_head
        self.n_layer = n_layer
        self.d_ff = d_ff
        self.dropout = dropout
        # dropout on attention probabilities (None = follow `dropout`,
        # preserving the classic behavior); the fused (pallas) attention
        # kernel runs only when the effective value is 0 (no in-kernel RNG)
        self.attn_dropout = dropout if attn_dropout is None else attn_dropout
        self.use_flash_attention = use_flash_attention
        # balanced causal layout when the sequence axis is ring-sharded
        self.ring_zigzag = False
        for field, value, known in (
                ('norm', norm, ('layer_norm', 'rms_norm')),
                ('position', position, ('sinusoid', 'rope', 'none')),
                ('ffn', ffn, ('gelu', 'moe', 'gated')),
                ('moe_score', moe_score, ('softmax', 'sigmoid')),
                ('attention', attention, ('mha', 'mla')),
                ('qk_norm', qk_norm, (False, True, 'head')),
                ('expert_form', expert_form, ('gated', 'relu2')),
                ('matmul_precision', matmul_precision, (None, 'highest')),
                ('norm_placement', norm_placement,
                 ('pre', 'post', 'sandwich'))):
            if value not in known:
                raise ValueError('LMConfig.%s=%r: expected one of %r'
                                 % (field, value, known))
        self.norm = norm
        self.rms_eps = rms_eps
        self.position = position
        self.rope_theta = rope_theta
        self.head_dim = head_dim or d_model // n_head
        self.qk_norm = qk_norm
        self.bias = bias
        self.ffn = ffn
        self.n_experts = n_experts
        self.experts_per_token = experts_per_token
        self.expert_width = expert_width
        self.norm_topk_prob = norm_topk_prob
        if ffn == 'moe' and not 0 < experts_per_token <= n_experts:
            raise ValueError('LMConfig.ffn=%r needs 0 < experts_per_token '
                             '<= n_experts, got %r of %r'
                             % (ffn, experts_per_token, n_experts))
        self.moe_score = moe_score
        self.routed_scale = routed_scale
        self.n_shared_experts = n_shared_experts
        self.expert_form = expert_form
        self.shared_expert_width = int(
            shared_expert_width or n_shared_experts * expert_width)
        self.experts_held = tuple(experts_held or (0, n_experts))
        self.n_dense_layers = n_dense_layers if ffn == 'moe' else 0
        self.attention = attention
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_dim = qk_nope_dim
        self.qk_rope_dim = qk_rope_dim
        self.v_head_dim = v_head_dim
        self.rope_interleave = rope_interleave
        self.n_kv_head = n_kv_head or n_head
        self.layer_types = tuple(layer_types or ('attention',) * n_layer)
        self.conv_kernel = conv_kernel
        self.tie_embeddings = tie_embeddings
        self.router_eps = router_eps
        self.sliding_window = int(sliding_window)
        self.global_rope = bool(global_rope)
        self.ssm_expand = int(ssm_expand)
        self.ssm_state = int(ssm_state)
        self.ssm_conv = int(ssm_conv)
        self.ssm_dt_rank = int(ssm_dt_rank or -(-d_model // 16))
        self.ssm_heads = int(ssm_heads)
        self.ssm_head_dim = int(ssm_head_dim)
        self.ssm_groups = int(ssm_groups)
        self.ssm_chunk = int(ssm_chunk)
        self.matmul_precision = matmul_precision
        self.attention_rope = dict(attention_rope or {})
        self.gdn_key_heads = int(gdn_key_heads)
        self.gdn_value_heads = int(gdn_value_heads)
        self.gdn_key_dim = int(gdn_key_dim)
        self.gdn_value_dim = int(gdn_value_dim)
        self.gdn_chunk = int(gdn_chunk)
        self.attention_gate = bool(attention_gate)
        self.rotary_dim = None if rotary_dim is None else int(rotary_dim)
        self.norm_zero_centred = bool(norm_zero_centred)
        self.shared_expert_gate = bool(shared_expert_gate)
        self.norm_placement = norm_placement
        self.gdn_allow_neg_eigval = bool(gdn_allow_neg_eigval)
        self.passes = int(passes)
        if norm_placement != 'pre' and norm != 'rms_norm':
            raise ValueError("LMConfig.norm_placement=%r is built with "
                             "norm='rms_norm' only, got %r"
                             % (norm_placement, norm))
        if self.passes < 1 or self.passes > 1 and (
                set(self.layer_types) != {'attention'}
                or attention != 'mha' or ffn == 'moe'):
            raise ValueError("LMConfig.passes=%r: one pass or more, and "
                             "more than one is built with 'attention' "
                             "layers, attention='mha' and a dense FFN only "
                             "(got layer_types=%r, attention=%r, ffn=%r)"
                             % (passes, self.layer_types, attention, ffn))
        if set(self.attention_rope) - set(ROPE_KEYS):
            raise ValueError('LMConfig.attention_rope=%r: expected keys of '
                             '%r' % (attention_rope, ROPE_KEYS))
        if len(self.layer_types) != n_layer or set(self.layer_types) - {
                'attention', 'conv', 'window', 'ssm', 'ssd', 'gdn', 'ffn'}:
            raise ValueError("LMConfig.layer_types=%r: expected %d of "
                             "'attention' | 'conv' | 'window' | 'ssm' | "
                             "'ssd' | 'gdn' | 'ffn'"
                             % (self.layer_types, n_layer))
        if self.n_gdn_layers and (
                min(gdn_key_heads, gdn_value_heads, gdn_key_dim,
                    gdn_value_dim, gdn_chunk) < 1
                or gdn_value_heads % gdn_key_heads):
            raise ValueError("LMConfig.layer_types has 'gdn' layers: they "
                             "need gdn_key_heads x gdn_key_dim keys, whole "
                             "groups of gdn_value_heads x gdn_value_dim "
                             "values a key head and a gdn_chunk, got %r x "
                             "%r, %r x %r, %r"
                             % (gdn_key_heads, gdn_key_dim, gdn_value_heads,
                                gdn_value_dim, gdn_chunk))
        if self.rotary_dim is not None and not (
                position == 'rope' and attention == 'mha'
                and 0 < self.rotary_dim <= self.head_dim
                and self.rotary_dim % 2 == 0):
            raise ValueError("LMConfig.rotary_dim=%r: an even part of "
                             "head_dim=%r under position='rope', attention="
                             "'mha'" % (rotary_dim, self.head_dim))
        if self.attention_gate and attention != 'mha':
            raise ValueError("LMConfig.attention_gate is built with "
                             "attention='mha' only")
        if self.norm_zero_centred and norm != 'rms_norm':
            raise ValueError("LMConfig.norm_zero_centred needs norm="
                             "'rms_norm', got %r" % (norm,))
        if self.shared_expert_gate and not (ffn == 'moe'
                                            and n_shared_experts):
            raise ValueError("LMConfig.shared_expert_gate gates a shared "
                             "expert: ffn='moe' with n_shared_experts")
        if self.n_ssd_layers and (
                ssm_heads < 1 or ssm_head_dim < 1 or ssm_groups < 1
                or ssm_heads % ssm_groups):
            raise ValueError("LMConfig.layer_types has 'ssd' layers: they "
                             "need ssm_heads x ssm_head_dim channels in "
                             "ssm_groups groups of whole heads, got %r x %r "
                             "in %r" % (ssm_heads, ssm_head_dim, ssm_groups))
        if (self.n_ssm_layers or self.n_ssd_layers or self.n_gdn_layers) \
                and self.ssm_conv - 1 > ssm_ops.TAIL_ROWS:
            raise ValueError("LMConfig.ssm_conv=%r: a state-space layer's "
                             "tail is at most %d rows a slot"
                             % (ssm_conv, ssm_ops.TAIL_ROWS))
        if bool(self.n_window_layers) != (self.sliding_window > 0):
            raise ValueError("LMConfig.layer_types=%r with LMConfig."
                             "sliding_window=%r: 'window' layers and a "
                             "window's size come together"
                             % (self.layer_types, sliding_window))
        if n_head % self.n_kv_head:
            raise ValueError('LMConfig.n_kv_head=%r does not divide '
                             'n_head=%r' % (self.n_kv_head, n_head))
        if attention == 'mla' and (self.n_kv_head != n_head
                                   or self.n_attn_layers != n_layer):
            raise ValueError("LMConfig.attention='mla' is built with "
                             "neither n_kv_head nor any layer_types but "
                             "'attention', got %r" % (self.layer_types,))
        if attention == 'mla' and not (
                position == 'rope' and q_lora_rank and kv_lora_rank
                and qk_nope_dim and qk_rope_dim and v_head_dim):
            raise ValueError("LMConfig.attention='mla' needs position="
                             "'rope' and its five sizes (q_lora_rank, "
                             "kv_lora_rank, qk_nope_dim, qk_rope_dim, "
                             "v_head_dim)")

    @property
    def kv_width(self):
        """Lanes of one cached row. Heads x head size of K (and of V, in
        its own pool); with latent attention the ONE row of a token,
        ``kv_lora_rank + qk_rope_dim`` numbers filled up with zeros to
        whole 128-lane tiles — the TPU stores the pool in such tiles
        whatever is declared, and the decode kernel copies whole ones
        (ops/mla_paged_decode_attention.py)."""
        if self.attention == 'mla':
            return -(-(self.kv_lora_rank + self.qk_rope_dim) // 128) * 128
        return self.n_kv_head * self.head_dim

    @property
    def n_conv_layers(self):
        return self.layer_types.count('conv')

    @property
    def n_window_layers(self):
        return self.layer_types.count('window')

    @property
    def n_ssm_layers(self):
        return self.layer_types.count('ssm')

    @property
    def n_ssd_layers(self):
        return self.layer_types.count('ssd')

    @property
    def ssm_inner(self):
        """Channels of a state-space layer's recurrence (``d_inner``)."""
        return self.ssm_expand * self.d_model

    @property
    def ssd_inner(self):
        """Channels of a Mamba-2 layer's recurrence: heads x head size
        (NOT ``ssm_expand x d_model``)."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssd_conv_width(self):
        """Channels of a Mamba-2 layer's convolution: x, B and C."""
        return self.ssd_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def n_gdn_layers(self):
        return self.layer_types.count('gdn')

    @property
    def gdn_inner(self):
        """Channels of a Gated DeltaNet layer's values (and of its gate
        ``z``): value heads x value size."""
        return self.gdn_value_heads * self.gdn_value_dim

    @property
    def gdn_conv_width(self):
        """Channels of a Gated DeltaNet layer's convolution: q, k and v."""
        return 2 * self.gdn_key_heads * self.gdn_key_dim + self.gdn_inner

    @property
    def n_attn_layers(self):
        """The GLOBAL attention layers: those of the K/V pools that the
        block allocator's tables address."""
        return self.n_layer - sum(self.layer_types.count(kind) for kind in (
            'conv', 'window', 'ssm', 'ssd', 'gdn', 'ffn'))

    def has_mixer(self, layer):
        """Whether `layer` has the mixer's sublayer (norm ``ln1``)."""
        return self.layer_types[layer] != 'ffn'

    def has_ffn(self, layer):
        """Whether `layer` has the FFN's sublayer (norm ``ln2``)."""
        return 'ffn' not in self.layer_types \
            or self.layer_types[layer] == 'ffn'

    def rotates(self, layer):
        """Whether `layer`'s q and k are rotated by the positions."""
        return self.position == 'rope' and (
            self.global_rope or self.layer_types[layer] == 'window')

    def rope(self, layer):
        """The rotation of `layer`'s q and k, as `layers.rotary_embedding`
        takes it: ``rope_theta`` and nothing else, but for an 'attention'
        layer of a model that gives those layers a rotation of their own
        (`attention_rope`)."""
        own = self.attention_rope \
            if self.layer_types[layer] == 'attention' else {}
        part = {} if self.rotary_dim is None \
            else {'rotary_dim': self.rotary_dim}
        return dict({'theta': self.rope_theta}, **dict(own, **part))

    def layer_ordinal(self, layer):
        """`layer`'s place among the layers of its kind: the `layer`
        attribute of its cache ops (a pool holds one kind only)."""
        return self.layer_types[:layer].count(self.layer_types[layer])

    def cache_ordinal(self, layer, loop_pass=0):
        """`layer`'s cache layer in its kind's pools at pass `loop_pass`
        (the `layer` attribute of its cache ops): the passes behind one
        another, each the kind's layers in order."""
        return loop_pass * self.layer_types.count(self.layer_types[layer]) \
            + self.layer_ordinal(layer)

    @property
    def attn_width(self):
        """Lanes of one token's attention output, all heads."""
        return self.n_head * (self.v_head_dim if self.attention == 'mla'
                              else self.head_dim)

    @property
    def n_moe_layers(self):
        return sum(self.has_ffn(i) for i in range(
            self.n_dense_layers, self.n_layer)) if self.ffn == 'moe' else 0


# `LMConfig.attention_rope`: the 'attention' layers' own base, and YaRN's
# parameters (`layers.rotary_embedding`)
ROPE_KEYS = ('theta', 'factor', 'original_max_position', 'beta_fast',
             'beta_slow', 'attention_factor')

_CLASSIC_BLOCK = (('norm', 'layer_norm'), ('position', 'sinusoid'),
                  ('qk_norm', False), ('bias', True), ('ffn', 'gelu'),
                  ('attention', 'mha'))


def _require_classic_block(cfg, who):
    """`who` writes the classic block out by hand: refuse a configuration
    it cannot express, naming the field."""
    classic = _CLASSIC_BLOCK + (
        ('head_dim', cfg.d_model // cfg.n_head),
        ('n_kv_head', cfg.n_head),
        ('layer_types', ('attention',) * cfg.n_layer),
        ('tie_embeddings', False), ('matmul_precision', None),
        ('attention_gate', False), ('rotary_dim', None),
        ('norm_zero_centred', False), ('shared_expert_gate', False),
        ('norm_placement', 'pre'), ('gdn_allow_neg_eigval', False),
        ('passes', 1))
    for field, value in classic:
        if getattr(cfg, field) != value:
            raise ValueError(
                "%s cannot express LMConfig.%s=%r (it builds %r): only the "
                "decode step and the prefill (build_lm_decode_step, "
                "build_lm_prefill_paged) build that block"
                % (who, field, getattr(cfg, field), value))


def multi_head_attention(x, cfg, prefix, mask_var=None, is_test=False,
                         seq_parallel=False, causal=False,
                         key_padding_bias=None):
    """Fused-QKV multi-head self-attention: one (D, 3D) matmul for Q,K,V
    (fewer, larger MXU matmuls than three separate projections).

    The flash branch hands the fused product ``[B, L, 3 * H * dh]`` to the
    `flash_attention` op AS IT IS and gets the context back ``[B, L, H *
    dh]``, as ``proj``'s `fc` reads it: no reshape, transpose, slice or
    squeeze op lies between the two `fc`s. Where the op's packed kernels
    apply (heads of 64 or 128 in whole 128-lane column blocks, no padding
    bias, no mesh axis over the heads or L: `ops/attention_ops.py`) no
    transpose runs on the device either; elsewhere the op turns the
    product head-major inside its lowering. The unfused branch keeps its
    ``[3, B, H, L, dh]`` transpose."""
    d, h = cfg.d_model, cfg.n_head
    dh = d // h
    qkv = layers.fc(input=x, size=3 * d, num_flatten_dims=2,
                    param_attr=ParamAttr(name=prefix + '.qkv.w'),
                    bias_attr=ParamAttr(name=prefix + '.qkv.b'))
    attn_drop = getattr(cfg, 'attn_dropout', 0.0)
    # the fused kernel supports causal masking and per-key padding biases
    # (key_padding_bias [B, L]); a full additive mask_var or active
    # attention dropout falls back to the unfused path
    use_flash = getattr(cfg, 'use_flash_attention', False) and \
        (causal or key_padding_bias is not None) and \
        mask_var is None and (is_test or not attn_drop)
    if use_flash:
        # fused causal attention (pallas on TPU): scores never leave VMEM
        helper_block = x.block
        ctx = helper_block.create_var(
            name=prefix + '.flash_out',
            shape=(-1, cfg.seq_len, d), dtype='float32')
        flash_inputs = {'QKV': [qkv]}
        if key_padding_bias is not None:
            flash_inputs['KeyPaddingBias'] = [key_padding_bias]
        helper_block.append_op(
            type='flash_attention',
            inputs=flash_inputs,
            outputs={'Out': [ctx]},
            attrs={'scale': dh ** -0.5, 'causal': bool(causal),
                   'num_heads': h,
                   'ring_zigzag': bool(getattr(cfg, 'ring_zigzag',
                                               False))})
    else:
        qkv = layers.reshape(qkv, shape=[0, cfg.seq_len, 3, h, dh])
        qkv = layers.transpose(qkv, perm=[2, 0, 3, 1, 4])  # (3, B, H, L, dh)
        q, k, v = (layers.squeeze(layers.slice(qkv, axes=[0], starts=[i],
                                               ends=[i + 1]), axes=[0])
                   for i in range(3))
        logits = layers.matmul(q, k, transpose_y=True, alpha=dh ** -0.5)
        if mask_var is not None:
            logits = layers.elementwise_add(logits, mask_var)
        if key_padding_bias is not None:
            # [B, L] per-key bias broadcasts over heads/query positions
            logits = layers.elementwise_add(
                logits, layers.reshape(key_padding_bias,
                                       [-1, 1, 1, cfg.seq_len]))
        weights = layers.softmax(logits)
        if attn_drop and not is_test:
            weights = layers.dropout(weights, dropout_prob=attn_drop,
                                     is_test=is_test,
                                     dropout_implementation='upscale_in_train')
        ctx = layers.matmul(weights, v)                # (B, H, L, dh)
        ctx = layers.transpose(ctx, perm=[0, 2, 1, 3])
        ctx = layers.reshape(ctx, shape=[0, cfg.seq_len, d])
    out = layers.fc(input=ctx, size=d, num_flatten_dims=2,
                    param_attr=ParamAttr(name=prefix + '.proj.w'),
                    bias_attr=ParamAttr(name=prefix + '.proj.b'))
    return out


def _entry_ln(x, residual, bna, name):
    """LayerNorm at a residual-stream read point. With ``residual`` (the
    pending FFN delta deferred from the previous block) the pair lowers as
    ONE fused residual-add + LN op — tier 'off' is bitwise
    elementwise_add + layer_norm, so legacy numerics hold. Returns
    (ln_out, resolved_stream)."""
    if residual is None:
        ln = layers.layer_norm(x, begin_norm_axis=bna,
                               param_attr=ParamAttr(name=name + '.w'),
                               bias_attr=ParamAttr(name=name + '.b'))
        return ln, x
    return layers.fused_layer_norm_residual(
        x, residual, begin_norm_axis=bna,
        param_attr=ParamAttr(name=name + '.w'),
        bias_attr=ParamAttr(name=name + '.b'))


def _norm(cfg, x, residual, bna, name, final=False):
    """The block's norm at a residual-stream read point, of whichever
    kind `cfg.norm` says: (normed, resolved_stream), the pending
    ``residual`` (or None) added first. LayerNorm: `_entry_ln`. With
    ``norm_placement='post'`` a sublayer reads the resolved stream as it
    is -- its norm is on its output (`_out_norm`) -- and only the ``final``
    norm is one; with ``'sandwich'`` it reads the normed stream AND its
    output is normed."""
    if cfg.norm == 'layer_norm':
        return _entry_ln(x, residual, bna, name)
    if residual is not None:
        x = layers.elementwise_add(x, residual)
    if cfg.norm_placement == 'post' and not final:
        return x, x
    return layers.rms_norm(x, begin_norm_axis=bna, epsilon=cfg.rms_eps,
                           param_attr=ParamAttr(name=name + '.w'),
                           zero_centred=cfg.norm_zero_centred), x


def _out_norm(cfg, delta, bna, name):
    """A sublayer's output as it joins the stream: normed by the weight
    that `_norm` left unused with ``norm_placement='post'``, by a weight
    of its own (``<name>_out.w``) with ``'sandwich'``, else as it is."""
    if cfg.norm_placement == 'pre':
        return delta
    if cfg.norm_placement == 'sandwich':
        name += '_out'
    return layers.rms_norm(delta, begin_norm_axis=bna, epsilon=cfg.rms_eps,
                           param_attr=ParamAttr(name=name + '.w'),
                           zero_centred=cfg.norm_zero_centred)


def _bias(cfg, name):
    return ParamAttr(name=name) if cfg.bias else False


def _heads_of(cfg, flat, p, which, pos, T, rotate):
    """One of q / k / v from its flat projection ([S, H*dh] decode rows,
    [1, T, H*dh] in a prefill; H the K/V heads for k and v): the optional
    q/k-norm (over the whole width before the split into heads, or over
    each head after it), the rotation by the fed positions where the
    layer rotates (`rotate`: its rotation's parameters, `LMConfig.rope`);
    laid out as the cache ops want it ([S, H, dh];
    [1, H, T, dh])."""
    h = cfg.n_head if which == 'q' else cfg.n_kv_head
    dh = cfg.head_dim
    rows = T is None
    normed = cfg.qk_norm and which != 'v'
    norm_attr = ParamAttr(name='%s.attn.%s_norm.w' % (p, which))
    if normed and cfg.qk_norm != 'head':
        flat = layers.rms_norm(
            flat, begin_norm_axis=1 if rows else 2, epsilon=cfg.rms_eps,
            param_attr=norm_attr, zero_centred=cfg.norm_zero_centred)
    x = layers.reshape(flat, shape=[-1, h, dh] if rows else [0, T, h, dh])
    if normed and cfg.qk_norm == 'head':
        x = layers.rms_norm(x, begin_norm_axis=2 if rows else 3,
                            epsilon=cfg.rms_eps, param_attr=norm_attr,
                            zero_centred=cfg.norm_zero_centred)
    if rotate and which != 'v':
        x = layers.rotary_embedding(x, pos, **rotate)
    return x if rows else layers.transpose(x, perm=[0, 2, 1, 3])


def _qkv(cfg, ln1, p, pos, T=None, layer=0):
    """The block's q, k, v from its normed input, and the attention's
    gate (None without `LMConfig.attention_gate`): the fused projection,
    then each prepared for the cache ops. ``T`` None: decode rows
    ``[S, d]`` -> three ``[S, H, dh]``; else one prompt ``[1, T, d]`` ->
    three ``[1, H, T, dh]``. K comes back as it is CACHED: after k-norm
    and rotation (`LMConfig.rotates(layer)`), on its ``n_kv_head`` heads
    (as V is). The gate comes back flat, ``[.., H x dh]``, as the
    attention's output is when `_gated` multiplies it in."""
    if cfg.attention == 'mla':
        return _mla_qkv(cfg, ln1, p, pos, T) + (None,)
    h, dh = cfg.n_head, cfg.head_dim
    wide = 2 if cfg.attention_gate else 1       # a head's q, then its gate
    ends = [w * dh for w in (wide * h, wide * h + cfg.n_kv_head,
                             wide * h + 2 * cfg.n_kv_head)]
    qkv = layers.fc(ln1, size=ends[-1],
                    num_flatten_dims=1 if T is None else 2,
                    param_attr=ParamAttr(name=p + '.attn.qkv.w'),
                    bias_attr=_bias(cfg, p + '.attn.qkv.b'))
    if not cfg.qk_norm and cfg.position == 'sinusoid' \
            and cfg.n_kv_head == h:
        if T is None:
            return _qkv_split_step(qkv, cfg) + [None]
        qkv = layers.reshape(qkv, shape=[0, T, 3, h, dh])
        qkv = layers.transpose(qkv, perm=[2, 0, 3, 1, 4])    # (3,1,H,T,dh)
        return [layers.squeeze(layers.slice(qkv, axes=[0], starts=[i],
                                            ends=[i + 1]), axes=[0])
                for i in range(3)] + [None]
    axis = 1 if T is None else 2
    lead = [-1] if T is None else [0, T]
    out, gate = [], None
    for which, start, end in zip('qkv', [0] + ends, ends):
        flat = layers.slice(qkv, axes=[axis], starts=[start], ends=[end])
        if which == 'q' and cfg.attention_gate:
            both = layers.reshape(flat, shape=lead + [h, 2 * dh])
            flat, gate = [
                layers.reshape(layers.slice(both, axes=[axis + 1],
                                            starts=[at], ends=[at + dh]),
                               shape=lead + [h * dh]) for at in (0, dh)]
        out.append(_heads_of(cfg, flat, p, which, pos, T,
                             cfg.rotates(layer) and cfg.rope(layer)))
    return out + [gate]


def _gated(ctx, gate):
    """The attention's flat output times ``sigmoid(gate)`` (`_qkv`'s; None:
    as it is)."""
    return ctx if gate is None \
        else layers.elementwise_mul(ctx, layers.sigmoid(gate))


def _mla_qkv(cfg, ln1, p, pos, T=None):
    """Latent attention's `_qkv`: (q, the row to cache, None). q ``[S, H,
    nope + rope]`` (``[1, T, H, nope + rope]`` in a prefill), its rotary
    part rotated. The row, laid out as the cache ops take a K of ONE head
    (``[S, 1, kv_width]``; ``[1, 1, T, kv_width]``): the normed latent,
    the ONE rotated rotary key of all heads, zeros up to `kv_width`.
    There is no V to cache."""
    h, nope, rope = cfg.n_head, cfg.qk_nope_dim, cfg.qk_rope_dim
    rank, nfd = cfg.kv_lora_rank, 1 if T is None else 2
    lead = [-1] if T is None else [0, T]

    def proj(x, size, name):
        return layers.fc(x, size=size, num_flatten_dims=nfd,
                         param_attr=ParamAttr(name='%s.attn.%s.w'
                                              % (p, name)),
                         bias_attr=False)

    def norm(x, name):
        return layers.rms_norm(
            x, begin_norm_axis=nfd, epsilon=cfg.rms_eps,
            param_attr=ParamAttr(name='%s.attn.%s_norm.w' % (p, name)))

    def rotated(x):
        return layers.rotary_embedding(x, pos, theta=cfg.rope_theta,
                                       interleave=cfg.rope_interleave)

    def cut(x, axis, start, end):
        return layers.slice(x, axes=[axis], starts=[start], ends=[end])

    q = layers.reshape(proj(norm(proj(ln1, cfg.q_lora_rank, 'q_a'), 'q_a'),
                            h * (nope + rope), 'q_b'),
                       shape=lead + [h, nope + rope])
    q = layers.concat([cut(q, nfd + 1, 0, nope),
                       rotated(cut(q, nfd + 1, nope, nope + rope))],
                      axis=nfd + 1)
    kv = proj(ln1, rank + rope, 'kv_a')
    k_rope = layers.reshape(
        rotated(layers.reshape(cut(kv, nfd, rank, rank + rope),
                               shape=lead + [1, rope])),
        shape=lead + [rope])
    row = layers.pad(
        layers.concat([norm(cut(kv, nfd, 0, rank), 'kv_a'), k_rope],
                      axis=nfd),
        paddings=[0, 0] * nfd + [0, cfg.kv_width - rank - rope])
    return q, layers.reshape(
        row, shape=[-1, 1, cfg.kv_width] if T is None
        else [0, 1, T, cfg.kv_width]), None


def _mla_attend(cfg, attention, q, cache, pos, tables, layer):
    """`attention` (layers.mla_decode_attention / mla_prefix_attention) of
    `q` against the latent pool, with the layer's up-projection."""
    p = 'layer_%d' % layer
    return attention(
        q, cache, pos, tables, layer,
        (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5, cfg.kv_lora_rank,
        cfg.qk_rope_dim, cfg.v_head_dim,
        up_k_attr=ParamAttr(name=p + '.attn.kv_b_k.w'),
        up_v_attr=ParamAttr(name=p + '.attn.kv_b_v.w'))


def _conv_mixer(cfg, ln1, p, nth, conv, num_flatten_dims):
    """LFM2's gated short convolution on the normed input: ``[B | C | u] =
    z W_in``, ``y = (C * conv(B * u)) W_out``, no bias. ``conv(g,
    weight_attr, nth)`` is the program's cache op (layers.short_conv_decode
    / short_conv_prefill) on the ``nth`` convolution layer's tails: the
    causal depthwise convolution of ``B * u`` behind the rows the pool
    holds, and the pool's update."""
    d = cfg.d_model

    def proj(x, size, which):
        return layers.fc(x, size=size, num_flatten_dims=num_flatten_dims,
                         param_attr=ParamAttr(name='%s.conv.%s.w'
                                              % (p, which)),
                         bias_attr=False)
    bcu = proj(ln1, 3 * d, 'in')
    b, c, u = [layers.slice(bcu, axes=[num_flatten_dims], starts=[i * d],
                            ends=[(i + 1) * d]) for i in range(3)]
    mixed = conv(layers.elementwise_mul(b, u),
                 ParamAttr(name=p + '.conv.w'), nth)
    return proj(layers.elementwise_mul(c, mixed), d, 'out')


def _ssm_mixer(cfg, ln1, p, nth, ssm, num_flatten_dims):
    """Jamba's Mamba-1 mixer on the normed input: ``[u | z] = h W_in``,
    the program's cache op on the ``nth`` state-space layer's rows
    (``ssm(u, z, prefix, nth)``: layers.ssm_decode / ssm_prefill — the
    convolution, the inner projections and norms, the recurrence, the
    gate), ``W_out``. No bias on either projection."""
    di = cfg.ssm_inner

    def proj(x, size, which):
        return layers.fc(x, size=size, num_flatten_dims=num_flatten_dims,
                         param_attr=ParamAttr(name='%s.ssm.%s.w'
                                              % (p, which)),
                         bias_attr=False)
    uz = proj(ln1, 2 * di, 'in')
    u, z = [layers.slice(uz, axes=[num_flatten_dims], starts=[i * di],
                         ends=[(i + 1) * di]) for i in range(2)]
    return proj(ssm(u, z, p + '.ssm', nth), cfg.d_model, 'out')


def _ssd_mixer(cfg, ln1, p, nth, ssd, num_flatten_dims):
    """Nemotron-H's Mamba-2 mixer on the normed input: ``[z | xBC | dt] =
    h W_in``, the program's cache op on the ``nth`` Mamba-2 layer's rows
    (``ssd(xbc, z, dt, prefix, nth)``: layers.ssd_decode / ssd_prefill --
    the convolution, the recurrence, the gate and the group norm),
    ``W_out``. No bias on either projection."""
    def proj(x, size, which):
        return layers.fc(x, size=size, num_flatten_dims=num_flatten_dims,
                         param_attr=ParamAttr(name='%s.ssd.%s.w'
                                              % (p, which)),
                         bias_attr=False)
    ends = [cfg.ssd_inner, cfg.ssd_inner + cfg.ssd_conv_width,
            cfg.ssd_inner + cfg.ssd_conv_width + cfg.ssm_heads]
    zxd = proj(ln1, ends[-1], 'in')
    z, xbc, dt = [layers.slice(zxd, axes=[num_flatten_dims], starts=[start],
                               ends=[end])
                  for start, end in zip([0] + ends, ends)]
    return proj(ssd(xbc, z, dt, p + '.ssd', nth), cfg.d_model, 'out')


def _gdn_mixer(cfg, ln1, p, nth, gdn, num_flatten_dims):
    """The Gated DeltaNet mixer on the block's input (normed, or with
    ``norm_placement='post'`` the stream): ``[q | k | v
    | z] = h W_in`` and ``[b | a] = h W_ba``, the program's cache op on the
    ``nth`` such layer's rows (``gdn(qkv, z, b, a, prefix, nth)``:
    layers.gdn_decode / gdn_prefill -- the convolution, the norms of q and
    k, the delta rule, the output norm and the gate), ``W_out``. No bias
    on any projection."""
    def proj(x, size, which):
        return layers.fc(x, size=size, num_flatten_dims=num_flatten_dims,
                         param_attr=ParamAttr(name='%s.gdn.%s.w'
                                              % (p, which)),
                         bias_attr=False)

    def cut(x, start, end):
        return layers.slice(x, axes=[num_flatten_dims], starts=[start],
                            ends=[end])
    cw, hv = cfg.gdn_conv_width, cfg.gdn_value_heads
    qkvz = proj(ln1, cw + cfg.gdn_inner, 'in')
    ba = proj(ln1, 2 * hv, 'ba')
    return proj(gdn(cut(qkvz, 0, cw), cut(qkvz, cw, cw + cfg.gdn_inner),
                    cut(ba, 0, hv), cut(ba, hv, 2 * hv), p + '.gdn', nth),
                cfg.d_model, 'out')


# the mixers that are no attention, by the layer's kind: each takes (cfg,
# ln1, prefix, ordinal, the program's cache op of the kind, the rows' axis)
_MIXERS = {'conv': _conv_mixer, 'ssm': _ssm_mixer, 'ssd': _ssd_mixer,
           'gdn': _gdn_mixer}


def _dense_ffn(x, width, d_model, name, num_flatten_dims, form='gated'):
    """``(silu(x W_g) * (x W_u)) W_d``, no bias: a dense SiLU-gated FFN
    (and an expert that every row goes through); with ``form='relu2'``
    the ungated ``relu(x W_u)^2 W_d``."""
    def proj(x, size, which):
        return layers.fc(x, size=size, num_flatten_dims=num_flatten_dims,
                         param_attr=ParamAttr(name='%s.%s.w'
                                              % (name, which)),
                         bias_attr=False)
    if form == 'relu2':
        return proj(layers.square(layers.relu(proj(x, width, 'up'))),
                    d_model, 'down')
    return proj(layers.elementwise_mul(layers.swish(proj(x, width, 'gate')),
                                       proj(x, width, 'up')),
                d_model, 'down')


def _ffn(cfg, ln2, p, num_flatten_dims, length=None, valid=None, layer=0):
    """The block's FFN on its normed input: (delta, routing). GELU: the
    fused tail, routing None. The ``n_dense_layers`` leading layers of an
    expert model: `_dense_ffn`, routing None. Experts: `layers.moe_ffn`
    over the rows, routing = (the ``[rows, experts_per_token]`` experts
    chosen, the int32 rows routed to each expert held here; ``length`` /
    ``valid`` say which rows are a request's and count)."""
    if cfg.ffn == 'gelu':
        # decode is inference-only: prob 0 / is_test keeps the op on the
        # RNG-free bind fast path (no per-step key derivation)
        return _ffn_tail(ln2, cfg, p, num_flatten_dims), None
    if cfg.ffn == 'gated' or layer < cfg.n_dense_layers:
        return _dense_ffn(ln2, cfg.d_ff, cfg.d_model, p + '.ffn',
                          num_flatten_dims), None
    shape = ln2.shape
    rows = ln2 if num_flatten_dims == 1 \
        else layers.reshape(ln2, shape=[-1, cfg.d_model])
    # only what departs from the softmax router over experts all held
    # here is said: that block's op is the one it always was
    router = {}
    if cfg.moe_score != 'softmax':
        router.update(score=cfg.moe_score, routed_scale=cfg.routed_scale,
                      select_bias_attr=ParamAttr(
                          name=p + '.moe.router.bias'))
    if cfg.experts_held != (0, cfg.n_experts):
        router['experts_held'] = cfg.experts_held
    if cfg.router_eps != 1e-20:
        router['router_eps'] = cfg.router_eps
    if cfg.expert_form != 'gated':
        router['form'] = cfg.expert_form
    out, idx, load = layers.moe_ffn(
        rows, cfg.n_experts, cfg.expert_width, cfg.experts_per_token,
        norm_topk_prob=cfg.norm_topk_prob, length=length, valid=valid,
        router_param_attr=ParamAttr(name=p + '.moe.router.w'),
        gate_param_attr=ParamAttr(name=p + '.moe.gate.w'),
        up_param_attr=ParamAttr(name=p + '.moe.up.w'),
        down_param_attr=ParamAttr(name=p + '.moe.down.w'), **router)
    if cfg.n_shared_experts:
        shared = _dense_ffn(rows, cfg.shared_expert_width, cfg.d_model,
                            p + '.moe.shared', 1, cfg.expert_form)
        if cfg.shared_expert_gate:
            # a scalar a row: [N, 1] against [N, d]
            shared = layers.elementwise_mul(shared, layers.sigmoid(layers.fc(
                rows, size=1, bias_attr=False,
                param_attr=ParamAttr(name=p + '.moe.shared_gate.w'))))
        out = layers.elementwise_add(out, shared)
    if num_flatten_dims != 1:
        out = layers.reshape(out, shape=[-1] + list(shape[1:]))
    return out, (idx, load)


def _lm_head(cfg, x):
    if cfg.tie_embeddings:
        # against the table where it lies, [V, d]: no transposed copy
        table = default_main_program().global_block().var('tok_emb.w')
        return layers.matmul(x, table, transpose_y=True)
    return layers.fc(x, size=cfg.vocab_size,
                     param_attr=ParamAttr(name='lm_head.w'),
                     bias_attr=False)


def _expert_outputs(out, tokens, routing):
    """What a decode program of an expert model adds to its outputs:
    'tokens_and_load', the tokens and the per-layer expert loads
    (``[n_layer * n_experts]``) as ONE int64 vector — one device-to-host
    transfer, as without experts (serving/generate.py splits it) — and
    'topk_idx', each layer's chosen experts, for the checks."""
    if routing:
        load = layers.cast(layers.concat([r[1] for r in routing], axis=0),
                           'int64')
        out['tokens_and_load'] = layers.concat([tokens, load], axis=0)
        out['topk_idx'] = [r[0] for r in routing]
    return out


def _ffn_tail(ln2, cfg, prefix, num_flatten_dims, dropout_prob=0.0,
              is_test=True):
    """The block's FFN tail — fc(d_ff, gelu) -> fc(d_model) -> dropout —
    as ONE fused_ffn_tail op (ops/ffn_ops.py). Parameter names, shapes
    and creation order are identical to the legacy fc pair, so startup
    programs and trained scopes are unchanged; tier 'off' replays the
    exact legacy op-by-op lowering."""
    return layers.fused_ffn_tail(
        ln2, cfg.d_ff, cfg.d_model,
        num_flatten_dims=num_flatten_dims,
        dropout_prob=dropout_prob, is_test=is_test,
        inner_param_attr=ParamAttr(name=prefix + '.ffn1.w'),
        inner_bias_attr=ParamAttr(name=prefix + '.ffn1.b'),
        param_attr=ParamAttr(name=prefix + '.ffn2.w'),
        bias_attr=ParamAttr(name=prefix + '.ffn2.b'))


def transformer_block(x, cfg, prefix, mask_var=None, is_test=False,
                      causal=False, key_padding_bias=None, residual=None,
                      defer_residual=False):
    """Pre-norm residual block.

    ``residual`` is the previous block's still-unadded FFN delta: when
    given, the entry LayerNorm fuses the pending residual add (ln1
    becomes a fused_layer_norm_residual site, completing the LN fusion
    across block boundaries). ``defer_residual=True`` returns
    ``(stream, delta)`` with THIS block's FFN output unadded, for the
    next block (or the final LN) to fuse; the default keeps the legacy
    single-tensor contract for external callers."""
    ln1, x = _entry_ln(x, residual, 2, prefix + '.ln1')
    attn = multi_head_attention(ln1, cfg, prefix + '.attn',
                                mask_var=mask_var, is_test=is_test,
                                causal=causal,
                                key_padding_bias=key_padding_bias)
    # fused residual-add + LayerNorm pair (kernel-tier unit): computes
    # x = x + attn and ln2 = LN(x) in one lowering — tier 'off' is
    # bitwise elementwise_add + layer_norm, so legacy numerics hold
    ln2, x = layers.fused_layer_norm_residual(
        x, attn, begin_norm_axis=2,
        param_attr=ParamAttr(name=prefix + '.ln2.w'),
        bias_attr=ParamAttr(name=prefix + '.ln2.b'))
    ff2 = _ffn_tail(ln2, cfg, prefix, 2,
                    dropout_prob=float(cfg.dropout or 0.0),
                    is_test=is_test)
    if defer_residual:
        return x, ff2
    return layers.elementwise_add(x, ff2)


def _name_program(name, cfg=None):
    """Name the program being built, unless whoever made it already has:
    its compiled XLA module is then jit_<name> in a device trace. With
    ``cfg`` (the serving programs) it also takes the model's
    `LMConfig.matmul_precision`."""
    program = default_main_program()
    if program.name == program.DEFAULT_NAME:
        program.name = name
    if cfg is not None:
        program.matmul_precision = cfg.matmul_precision


def build_lm(cfg=None, is_test=False):
    """Causal LM: feeds {'tokens', 'labels'} of shape (B, L) int64; returns
    (tokens, labels, logits, avg_loss)."""
    cfg = cfg or LMConfig()
    _require_classic_block(cfg, 'build_lm')
    _name_program('lm_eval' if is_test else 'lm_train')
    tokens = layers.data(name='tokens', shape=[cfg.seq_len], dtype='int64')
    labels = layers.data(name='labels', shape=[cfg.seq_len], dtype='int64')

    emb = layers.embedding(
        tokens, size=[cfg.vocab_size, cfg.d_model], dtype='float32',
        param_attr=ParamAttr(name='tok_emb.w'))
    x = layers.add_position_encoding(emb, alpha=1.0, beta=1.0)
    if cfg.dropout and not is_test:
        x = layers.dropout(x, dropout_prob=cfg.dropout, is_test=is_test,
                           dropout_implementation='upscale_in_train')

    attn_drop = getattr(cfg, 'attn_dropout', 0.0)
    flash_ok = getattr(cfg, 'use_flash_attention', False) and \
        (is_test or not attn_drop)
    if flash_ok:
        mask_var = None          # causal masking fused into the kernel
    else:
        causal_mask = np.triu(np.full((cfg.seq_len, cfg.seq_len), -1e9,
                                      dtype='float32'), k=1)
        mask_var = layers.assign(causal_mask)

    block_outputs = []
    # canonical (stream, pending-delta) entry for the layer run: a zero
    # delta ahead of block 0 makes EVERY block lower the same op sequence
    # (fused entry LN), which the pipeline transpiler's repeated-layer
    # detection requires; x + x*0 is bitwise x, so numerics are unchanged
    delta = layers.scale(x, scale=0.0)
    for i in range(cfg.n_layer):
        x, delta = transformer_block(x, cfg, 'layer_%d' % i,
                                     mask_var=mask_var, is_test=is_test,
                                     causal=flash_ok, residual=delta,
                                     defer_residual=True)
        block_outputs.append(x)
    # per-layer boundaries for rematerialization, stashed on the PROGRAM
    # (names are per-program; stale names raise loudly at lowering):
    # append_backward(checkpoints=prog._lm_checkpoint_vars) trades
    # recompute FLOPs for activation HBM (core/lowering.py
    # _lower_with_remat). cfg.block_outputs mirrors the LAST build for
    # convenience in single-program scripts. With the FFN delta deferred
    # across block boundaries, each boundary is the post-attention
    # stream; the pending delta rides along as a second saved tensor per
    # boundary (segment lowering carries any crossing var generically).
    cfg.block_outputs = block_outputs
    tokens.block.program._lm_checkpoint_vars = block_outputs
    # training-health activation taps: the same residual-stream boundaries
    # double as the health observatory's activation-RMS sites — they
    # survive remat lowering (they ARE the remat segment outputs)
    tokens.block.program._health_act_taps = tuple(
        v.name for v in block_outputs)
    x, _ = _entry_ln(x, delta, 2, 'final_ln')
    logits = layers.fc(input=x, size=cfg.vocab_size, num_flatten_dims=2,
                       param_attr=ParamAttr(name='lm_head.w'),
                       bias_attr=False)
    flat_logits = layers.reshape(logits, shape=[-1, cfg.vocab_size])
    flat_labels = layers.reshape(labels, shape=[-1, 1])
    loss = layers.softmax_with_cross_entropy(flat_logits, flat_labels)
    avg_loss = layers.mean(loss)
    return tokens, labels, logits, avg_loss


# ---------------------------------------------------------------------------
# Generative decode programs (serving/generate.py)
#
# Two program shapes drive autoregressive generation against ONE persistent
# device-resident KV cache: a pool of fixed-size blocks,
# [num_blocks, layers, block_size, heads * head_dim] persistable buffers
# shared BY NAME with every program in the engine's scope — like params,
# the cache is ordinary executor state, so donation updates it in place.
# A page (one block of one layer) is one contiguous [block_size, d_model]
# run of HBM, addressed through runtime-fed per-slot block tables
# (ops/kv_cache_ops.py). The table is an ordinary feed, so the program
# count and every compiled signature stay fixed — serving/generate.py's
# allocator decides the physical layout per request at admission time.
#
# - build_lm_prefill_paged: one compiled signature per prompt bucket. Runs
#   the causal forward of ONE prompt suffix (padded to the bucket) against
#   whatever prefix the slot's table already holds, deposits its K/V rows
#   into the slot's blocks, and emits the first generated token (the
#   sampled token at the last REAL position).
# - build_lm_decode_step: ONE compiled signature per engine. Advances every
#   slot one token: deposits each slot's new K/V at its own position and
#   attends against its cached history (`kv_decode_attention_paged` reads
#   each slot's live pages in place from the pool — a Pallas kernel on the
#   chip, ops/paged_decode_attention.py; the prefill and verify programs
#   still gather a table row's pages into a dense K/V first). All ops are
#   slot-row-independent, so requests admitted/evicted at token boundaries
#   never perturb their neighbors' numerics (the parity contract
#   tests/test_generate.py pins).
#
# Parameter names match build_lm exactly — a scope trained (or loaded) for
# the LM serves decode without any renaming.
#
# A model with WINDOW or STATE-SPACE layers (`LMConfig.layer_types`
# 'window', 'ssm', 'ssd') declares further pools, sized by the engine's slots
# and served by no allocator, and both programs take the feed that indexes
# them (`cache_pools`, `_slot_feeds`): slot i's ring of `window_ring` blocks
# while it is resident, its row i + 1 (0 for a row that sits a step out:
# ops/ssm_ops.py). Each attention layer's ops get its kind's pool, table and
# bound; only the window layers rotate q and k where `global_rope` is off.
#
# The decode step (and the prefill, for the FIRST token) ends in the
# `sample_next_token` op: per-slot temperature / top-k / top-p feeds plus
# a host-fed uniform drive sampling; temperature 0 rows return the bitwise
# argmax. The op decides on the device from its temperature feed (one
# program, no engine option): a step whose rows are all greedy computes
# the argmax and nothing else; a step with a sampled row sorts the
# vocabulary once for every row (the sort carries the values, nothing
# gathers [rows, vocab]) and its greedy rows are still the argmax.
#
# SPECULATIVE decoding (PR 13) adds two program shapes:
# - build_lm_drafter: spec_k greedy decode steps UNROLLED in-program
#   (each one the same `_decode_tower` as the decode step), the draft
#   model's K proposals in one dispatch.
# - build_lm_verify: the target scores spec_k + 1 positions per slot in
#   one batched step (span cache write + per-row-masked attention), the
#   bitwise acceptance oracle for the drafts.
# serving/generate.py owns the host-side accept/rollback protocol.
# ---------------------------------------------------------------------------

KV_CACHE_K = 'gen_kv_k'
KV_CACHE_V = 'gen_kv_v'
CONV_CACHE = 'gen_conv_tail'
WINDOW_CACHE_K = 'gen_kv_window_k'
WINDOW_CACHE_V = 'gen_kv_window_v'
SSM_STATE = 'gen_ssm_state'
SSM_TAIL = 'gen_ssm_tail'
SSD_STATE = 'gen_ssd_state'
SSD_TAIL = 'gen_ssd_tail'
GDN_STATE = 'gen_gdn_state'
GDN_TAIL = 'gen_gdn_tail'


def window_ring(cfg, block_size):
    """Blocks a slot owns in the window layers' pools, the columns of its
    window table: logical block ``b`` lies in column ``b % ring``. The
    keys a query sees, ``pos - sliding_window + 1 .. pos``, lie in at most
    ``ceil(sliding_window / block_size) + 1`` blocks (one more than the
    window fills when it does not start on a block's first row). The
    ring is one block more than that, ISSUE 41's size (64 x 6 blocks): no
    case needs the sixth -- a step writes its row before it attends, a
    chunk attends before it writes, and the block either opens lies a
    whole ring behind the oldest key still seen -- and ROADMAP R5 queues
    taking it out."""
    return -(-cfg.sliding_window // block_size) + 2


def window_pool_blocks(cfg, slots, block_size, shared=False):
    """Blocks of the window layers' pools: every slot's ring, and block 0,
    the trash block (an idle slot's table row is all zero). `shared` (the
    engine shares prefixes): and half a ring a slot for the blocks that the
    prefix cache alone holds -- a prefix's resume point needs ``ring - 2``
    of them (`Pool.reach`), so half the slots can each have come from a
    prefix of their own before the cache lets one go."""
    rings = slots * window_ring(cfg, block_size)
    return rings + 1 + (rings // 2 if shared else 0)


def snapshot_rows(slots, shared=False):
    """Spare rows of the 'row' pools behind the slots' own: the SNAPSHOT
    rows that an engine which shares prefixes keeps a recurrent state in at
    a block's edge (serving/kv_blocks.py `SlotRows`), one a slot -- every
    resident can have come from a document of its own, or a few documents
    keep a row a prefill chunk each, before the cache gives one up; none
    where nothing is shared."""
    return int(slots) if shared else 0


# A pool of a model's programs, one row of `cache_pools`' table. `index`:
# what indexes its leading dimension -- 'block', the allocator's block ids;
# 'ring', a slot's ring of blocks; 'row', a slot's row (`INDEX_FEEDS`: the
# feed each comes in). `rewinds`: a rejected draft can be rewound from it
# (rows past the accepted write head are masked, nothing is copied).
# `copies`: a shared block's entry can be copied and resumed at its last
# row. `why`: what an engine says when it refuses an option over the pool.
# `books`: the series the engine books for the pool's ``shape[1]`` layers,
# once a kind -- 'step': (series, rows a slot a decode step reads at most;
# None: all up to its position); 'prefill': the REAL rows a prefill walks;
# 'resume': the dispatches that resume past position 0 from what it holds;
# 'hit': the admissions that resumed at a shared prefix's edge over it.
# `reach`: the rows behind a position that a query there still reads of the
# pool (None: every row, or a state) -- what a request that resumes at a
# shared prefix's edge has to find. Every pool can be resumed so
# (`prefix_sharing`): a 'block' pool by the shared blocks themselves, a
# 'ring' pool by the blocks the prefix cache keeps of it, a 'row' pool by a
# SNAPSHOT row, a copy of the slot's row of every 'row' pool taken where a
# prefill dispatch ended on a block's edge (`snapshot_rows`).
Pool = collections.namedtuple(
    'Pool', 'name shape index rewinds copies why books reach')
INDEX_FEEDS = {'block': 'gen_btab', 'ring': 'gen_wtab', 'row': 'gen_srow'}


def cache_pools(cfg, num_blocks=0, block_size=1, slots=None, shared=False):
    """The pools a model's programs declare, in the order of their state:
    the ONE place that maps a layer kind to a pool, and all an engine knows
    of a kind (serving/generate.py walks it). A pool the slots size has
    shape None where ``slots`` is not given. `shared`: the engine shares
    prefixes, and a pool whose blocks its prefix cache holds beside the
    slots' has room for them (`window_pool_blocks`, `snapshot_rows`).

    Indexed by the block allocator's ids: K and V apart, the GLOBAL
    attention layers' pages -- a cache layer a PASS of each
    (`LMConfig.passes`: the first pool whose layers are not the weights'
    layers; an engine reads its layers off the pool's ``shape[1]``) --, or
    with latent attention the ONE pool of latent rows (under K's name); with
    convolution layers their tails too,
    a block's ``conv_kernel - 1`` rows a layer. With window layers, indexed
    by the slots' rings (`window_ring`): those layers' K and V. With
    state-space layers, indexed by the slots' rows (slot ``i`` has row ``i
    + 1``; row 0 is the trash row): the state (the channels minor: whole
    vregs of lanes) and the tail (the ``ssm_conv - 1`` rows a layer keeps in
    a sublane tile of their own: ops/ssm_ops.py has what a padded one cost).
    With Mamba-2 layers, the same of theirs under the same rows: the state
    ``[N, heads x head size]`` a layer (ops/ssd_ops.py says why that way
    round) and the tail over all the convolution's channels. With Gated
    DeltaNet layers, theirs: the state ``[key dim, value heads x value
    dim]`` a layer (ops/gdn_ops.py) and the tail over q, k and v."""
    pools = []

    def kind(shapes, index, rewinds, copies, why=None, reach=None, **books):
        sized = index == 'block' or slots is not None
        for i, (name, shape) in enumerate(shapes):
            pools.append(Pool(name, shape if sized else None, index, rewinds,
                              copies, why, {} if i else books, reach))

    latent = cfg.attention == 'mla'
    n = slots or 0
    # a 'row' pool's rows: the trash row, a row a slot, the snapshot rows
    rows = n + 1 + snapshot_rows(n, shared)
    # a cache layer a pass of every layer (`LMConfig.cache_ordinal`)
    kv = (num_blocks, cfg.passes * cfg.n_attn_layers, block_size,
          cfg.kv_width)
    kind([(KV_CACHE_K, kv)] + [(KV_CACHE_V, kv)] * (not latent), 'block',
         True, True, step=('kv_latent_tokens_read_total' if latent
                           else 'kv_tokens_read_total', None))
    if cfg.n_conv_layers:
        kind([(CONV_CACHE, (num_blocks, cfg.n_conv_layers,
                            cfg.conv_kernel - 1, cfg.d_model))],
             'block', False, False,
             "a rejected draft rewinds positions, and a convolution layer's "
             "tail in the block pool cannot be rewound (it holds the last "
             "rows written, not every row)",
             resume='conv_tail_resumes_total')
    if cfg.n_window_layers:
        kv = (window_pool_blocks(cfg, n, block_size, shared),
              cfg.n_window_layers, block_size, cfg.kv_width)
        kind([(WINDOW_CACHE_K, kv), (WINDOW_CACHE_V, kv)], 'ring', False,
             False,
             "a window layer's blocks are a ring whose columns its slot "
             "hands on -- a rejected draft cannot be unwound from it (a "
             "block the window left behind may be another tenant's by "
             "then); a shared prefix is resumed from a block's edge, where "
             "the prefix cache still holds the window's blocks before it",
             reach=cfg.sliding_window - 1,
             step=('kv_window_tokens_read_total', cfg.sliding_window),
             hit='kv_window_prefix_resumes_total')
    if cfg.n_ssm_layers:
        kind([(SSM_STATE, (rows, cfg.n_ssm_layers, cfg.ssm_state,
                           cfg.ssm_inner)),
              (SSM_TAIL, (rows, cfg.n_ssm_layers, ssm_ops.TAIL_ROWS,
                          cfg.ssm_inner))], 'row', False, False,
             "a state-space layer's state is a row a slot, the recurrence "
             "up to the slot's last position -- a rejected draft cannot be "
             "unwound from it (a shared prefix is resumed from a snapshot "
             "row, taken at a block's edge)", reach=1,
             step=('ssm_state_rows_updated_total', 1),
             prefill='ssm_prefill_rows_total',
             resume='ssm_state_resumes_total')
    if cfg.n_ssd_layers:
        kind([(SSD_STATE, (rows, cfg.n_ssd_layers, cfg.ssm_state,
                           cfg.ssd_inner)),
              (SSD_TAIL, (rows, cfg.n_ssd_layers, ssm_ops.TAIL_ROWS,
                          cfg.ssd_conv_width))], 'row', False, False,
             "a Mamba-2 layer's state is a row a slot, every head's matrix "
             "as of the slot's last position -- a rejected draft cannot be "
             "unwound from it (a shared prefix is resumed from a snapshot "
             "row, taken at a block's edge)", reach=1,
             step=('ssd_state_rows_updated_total', 1),
             prefill='ssd_prefill_rows_total',
             resume='ssd_state_resumes_total')
    if cfg.n_gdn_layers:
        kind([(GDN_STATE, (rows, cfg.n_gdn_layers, cfg.gdn_key_dim,
                           cfg.gdn_inner)),
              (GDN_TAIL, (rows, cfg.n_gdn_layers, ssm_ops.TAIL_ROWS,
                          cfg.gdn_conv_width))], 'row', False, False,
             "a Gated DeltaNet layer's state is a row a slot, every value "
             "head's keys-by-values matrix as of the slot's last position "
             "-- a rejected draft cannot be unwound from it: the delta "
             "rule's correction is not undone by masking rows (a shared "
             "prefix is resumed from a snapshot row, taken at a block's "
             "edge)", reach=1,
             step=('gdn_state_rows_updated_total', 1),
             prefill='gdn_prefill_rows_total',
             resume='gdn_state_resumes_total')
    return tuple(pools)


def kv_cache_names(cfg):
    """The names of `cache_pools`' pools, in its order."""
    return tuple(pool.name for pool in cache_pools(cfg))


def kv_cache_shapes(cfg, num_blocks, block_size, slots=None, shared=False):
    """name -> shape of every pool of `cache_pools`; the window and the
    state-space layers' are sized by the engine's ``slots`` alone."""
    pools = cache_pools(cfg, num_blocks, block_size, slots, shared)
    if any(pool.shape is None for pool in pools):
        raise ValueError("LMConfig.layer_types=%r: the window and the "
                         "state-space layers' pools are sized by the slots"
                         % (cfg.layer_types,))
    return {pool.name: pool.shape for pool in pools}


def _declare_paged_kv_caches(block, cfg, num_blocks, block_size, slots=None,
                             shared=False):
    """name -> var of every pool of `cache_pools`."""
    return {name: block.create_var(name=name, shape=shape, dtype='float32',
                                   persistable=True, stop_gradient=True)
            for name, shape in kv_cache_shapes(cfg, num_blocks, block_size,
                                               slots, shared).items()}


def _slot_feeds(cfg, block_size):
    """index -> the feed of every index a pool of `cache_pools` has beside
    'block' ('gen_btab' is each builder's own): the slots' window tables
    ``[rows, window_ring]``, the slots' rows in the state-space layers'
    pools ``[rows, 1]`` (0: none, the trash row)."""
    present = {pool.index for pool in cache_pools(cfg)}
    return {index: layers.data(name=INDEX_FEEDS[index], shape=[width],
                               dtype='int64')
            for index, width in (('ring', window_ring(cfg, block_size)),
                                 ('row', 1)) if index in present}


SAMPLE_FEEDS = ('gen_temp', 'gen_topk', 'gen_topp', 'gen_u')


def _sampling_inputs():
    """Per-row sampling-control feeds ([rows, 1]; [1, 1] in prefill):
    temperature, top-k, top-p, and the host-drawn uniform."""
    return tuple(layers.data(name=name, shape=[1], dtype=dtype)
                 for name, dtype in zip(SAMPLE_FEEDS, (
                     'float32', 'int64', 'float32', 'float32')))


def _append_sample_op(block, logits, sample_vars, out_name):
    temp, topk, topp, u = sample_vars
    out = block.create_var(name=out_name, shape=(-1,), dtype='int64')
    block.append_op(
        type='sample_next_token',
        inputs={'Logits': [logits], 'Temp': [temp], 'TopK': [topk],
                'TopP': [topp], 'U': [u]},
        outputs={'Out': [out]})
    return out


def _qkv_split_step(qkv, cfg):
    """[S, 3d] -> three [S, H, dh], with the same 3/h/dh unpacking order as
    build_lm's reshape (q first, then k, then v)."""
    h, dh = cfg.n_head, cfg.d_model // cfg.n_head
    qkv = layers.reshape(qkv, shape=[-1, 3, h, dh])
    parts = []
    for i in range(3):
        parts.append(layers.squeeze(
            layers.slice(qkv, axes=[1], starts=[i], ends=[i + 1]),
            axes=[1]))
    return parts


# `LMConfig.passes`: what a mass of 1 reads as in the int64 vector a decode
# step returns its exit masses in, behind its tokens (`_exit_masses`)
EXIT_MASS_ONE = 1 << 20
# ... and the scope the ops of pass ``t`` lower under (core/lowering.py
# `trace_scope`; the decode attention's kernel takes it into its name)
LOOP_PASS_SCOPE = 'loop_pass_%d'


@contextlib.contextmanager
def _loop_pass(cfg, block, t):
    """The ops appended inside are pass `t`'s of `LMConfig.passes`: each
    carries the pass as its ``trace_scope`` (a one-pass model's carry
    nothing). Yields what its intermediate names take behind them."""
    start = len(block.ops)
    yield '.pass%d' % t if cfg.passes > 1 else ''
    if cfg.passes > 1:
        for op in block.ops[start:]:
            op.set_attr('trace_scope', LOOP_PASS_SCOPE % t)


def _close_pass(cfg, x, delta, bna, gates):
    """The end of a pass: the final norm on the stream (the SAME after
    every pass: its output is the next pass's input, the last one's the
    head's), and with `LMConfig.passes` the exit gate read on it,
    ``sigmoid(x w + b)`` a row, appended to `gates` (None: nobody reads
    it -- a prefill's rows are not a step's, and a program that lists the
    gate's parameters and never reads them has no layout to stage them
    in)."""
    x, _ = _norm(cfg, x, delta, bna, 'final_ln', final=True)
    if gates is not None and cfg.passes > 1:
        gates.append(layers.sigmoid(layers.fc(
            x, size=1, num_flatten_dims=bna,
            param_attr=ParamAttr(name='exit_gate.w'),
            bias_attr=ParamAttr(name='exit_gate.b'))))
    return x


def _exit_masses(gates, valid):
    """``[passes]`` int64: the exit distribution's mass a pass, ``p_t =
    lambda_t prod_{s<t} (1 - lambda_s)`` and the last pass the rest, summed
    over the live rows (`valid` ``[S, 1]``, zero = idle slot) in units of
    1 / `EXIT_MASS_ONE` -- a row's masses sum to 1."""
    live = layers.cast(layers.cast(valid, 'bool'), 'float32')
    masses = []
    for lam in gates[:-1]:
        masses.append(layers.elementwise_mul(live, lam))
        live = layers.elementwise_mul(
            live, layers.scale(lam, scale=-1.0, bias=1.0))
    masses.append(live)
    total = layers.concat([layers.reduce_sum(m, dim=[0]) for m in masses],
                          axis=0)
    return layers.cast(layers.scale(total, scale=float(EXIT_MASS_ONE),
                                    bias=0.5), 'int64')


def _decode_tower(cfg, x, cache_write, attend, tag='', head=True,
                  pos=None, valid=None, routing=None, mixers=None,
                  gates=None):
    """One decode-position transformer tower over per-slot row state
    ``x`` ([S, d]: token embedding, + position encoding where positions
    are added). The cache write and cached attention are delegated to
    closures so the SAME structural body serves the plain decode step,
    each of the drafter's unrolled steps, and any future cached-decode
    flavor — per-position numerics can never drift between them. Norm,
    q/k preparation and FFN are `cfg`'s (`_norm`, `_qkv`, `_ffn`).
    Returns logits [S, V].

    ``tag`` disambiguates intermediate var names when the tower is
    instantiated more than once in one program (the drafter's unroll).
    ``head=False`` skips the final norm + LM head and returns
    None — the drafter's trailing write-only step needs every layer's
    K/V deposited but no logits. ``pos`` ([S, 1], rotary positions),
    ``valid`` ([S, 1], zero = idle slot) and ``routing`` (a list that
    takes each layer's `_ffn` routing) serve the blocks that need them;
    ``mixers`` has the program's cache op of every kind in `_MIXERS`
    the model has. A layer is the sublayers `cfg.has_mixer` and
    `cfg.has_ffn` give it, each behind its norm.
    The cache closures get a layer's cache layer in its kind's pools
    (`LMConfig.cache_ordinal`: its ordinal among the layers of its kind,
    behind the earlier passes') and the kind (``'attention'`` |
    ``'window'``): a pool holds one kind. With `LMConfig.passes` the layer
    loop runs that many times over the same parameters, `_close_pass`
    after each (``gates`` takes each pass's exit gate); intermediate names
    carry the pass, parameter names do not."""
    delta = None             # previous layer's deferred FFN output
    block = x.block
    for t in range(cfg.passes):
        with _loop_pass(cfg, block, t) as pass_tag:
            for i in range(cfg.n_layer):
                p = 'layer_%d' % i
                nth, kind = cfg.cache_ordinal(i, t), cfg.layer_types[i]
                if cfg.has_mixer(i):
                    ln1, x = _norm(cfg, x, delta, 1, p + '.ln1')
                    if kind in _MIXERS:
                        delta = _MIXERS[kind](cfg, ln1, p, nth,
                                              mixers[kind], 1)
                    else:
                        q, k, v, gate = _qkv(cfg, ln1, p, pos,
                                             layer=i)        # [S, H, dh]
                        cache_write(k, v, nth, kind)
                        if not head and i == cfg.n_layer - 1:
                            # write-only tower, last layer: nothing
                            # consumes x past this K/V deposit —
                            # attention/proj/ffn are dead compute
                            return None
                        ctx = attend(q, nth, p + tag + pass_tag, kind)
                        delta = layers.fc(
                            _gated(layers.reshape(
                                ctx, shape=[-1, cfg.attn_width]), gate),
                            size=cfg.d_model,
                            param_attr=ParamAttr(name=p + '.attn.proj.w'),
                            bias_attr=_bias(cfg, p + '.attn.proj.b'))
                    delta = _out_norm(cfg, delta, 1, p + '.ln1')
                if cfg.has_ffn(i):
                    ln2, x = _norm(cfg, x, delta, 1, p + '.ln2')
                    delta, routed = _ffn(cfg, ln2, p, 1, valid=valid,
                                         layer=i)
                    delta = _out_norm(cfg, delta, 1, p + '.ln2')
                    if routed is not None:
                        routing.append(routed)
            if not head:
                return None
            x, delta = _close_pass(cfg, x, delta, 1, gates), None
    return _lm_head(cfg, x)                                  # [S, V]


def build_lm_decode_step(cfg, slots, max_len, block_size, num_blocks,
                         shared=False):
    """Single-token decode step over ALL cache slots. `shared`: the engine
    shares prefixes (`cache_pools`: the size of the window layers' pools).

    Feeds: 'gen_tokens' [slots, 1] int64 (each slot's last token),
    'gen_pos' [slots, 1] int64 (the position each slot writes this step),
    the `SAMPLE_FEEDS` quad [slots, 1] (temperature / top-k / top-p /
    host uniform; all-zero = bitwise greedy), and 'gen_btab'
    [slots, max_len // block_size] int64 per-slot block tables; with window
    or state-space layers also their `_slot_feeds`, the slots' rings and
    rows in those layers' pools (row 0: it sits this step out). Returns
    {'tokens', 'pos', 'logits', 'next_tokens', 'k_cache', 'v_cache'} —
    fetch 'next_tokens' ([slots] int64). With experts
    (`cfg.ffn == 'moe'`) also 'tokens_and_load' — next_tokens and the
    [n_layer * n_experts] expert loads of the live slots' rows in one
    int64 vector: fetch that INSTEAD — and 'topk_idx'
    (`_expert_outputs`). With `LMConfig.passes` 'tokens_and_load' is
    next_tokens and the [passes] exit masses of the live slots' rows
    (`_exit_masses`), and 'exit_gates' each pass's gate ``[slots, 1]``."""
    _name_program('lm_decode_step', cfg)
    d, h, dh = cfg.d_model, cfg.n_head, cfg.head_dim
    tokens = layers.data(name='gen_tokens', shape=[1], dtype='int64')
    pos = layers.data(name='gen_pos', shape=[1], dtype='int64')
    sample_vars = _sampling_inputs()
    block = tokens.block
    mb = max_len // block_size
    btab = layers.data(name='gen_btab', shape=[mb], dtype='int64')
    feeds = _slot_feeds(cfg, block_size)
    pools = _declare_paged_kv_caches(block, cfg, num_blocks, block_size,
                                     slots, shared)
    kc, vc = pools[KV_CACHE_K], pools.get(KV_CACHE_V)
    window = (pools.get(WINDOW_CACHE_K), pools.get(WINDOW_CACHE_V)), \
        feeds.get('ring')

    x = layers.embedding(
        tokens, size=[cfg.vocab_size, d], dtype='float32',
        param_attr=ParamAttr(name='tok_emb.w'))              # [S, d]
    if cfg.position == 'sinusoid':
        pe = layers.assign(position_encoding_table(max_len, d))
        x = layers.elementwise_add(x, layers.gather(pe, pos))

    def conv(g, weight_attr, layer):
        return layers.short_conv_decode(
            g, pools[CONV_CACHE], pos, btab, layer, block_size,
            cfg.conv_kernel, param_attr=weight_attr)

    def ssm(u, z, prefix, layer):
        return layers.ssm_decode(
            u, z, pools[SSM_STATE], pools[SSM_TAIL], feeds['row'], layer,
            prefix, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_dt_rank,
            epsilon=cfg.rms_eps)

    def ssd(xbc, z, dt, prefix, layer):
        return layers.ssd_decode(
            xbc, z, dt, pools[SSD_STATE], pools[SSD_TAIL], feeds['row'],
            layer, prefix, cfg.ssm_groups, cfg.ssm_conv, epsilon=cfg.rms_eps)

    def gdn(qkv, z, b, a, prefix, layer):
        return layers.gdn_decode(
            qkv, z, b, a, pools[GDN_STATE], pools[GDN_TAIL], feeds['row'],
            layer, prefix, cfg.gdn_key_heads, cfg.ssm_conv,
            epsilon=cfg.rms_eps, allow_neg_eigval=cfg.gdn_allow_neg_eigval)

    def cache_write(k, v, layer, kind):
        # a window layer writes into its slot's ring: the table's column
        # is the logical block modulo the table's width
        caches, table, ring = window + ({'ring': True},) \
            if kind == 'window' else ((kc, vc), btab, {})
        for cache, new in zip(caches, (k, v)):
            if cache is None:       # latent attention: no V pool
                continue
            block.append_op(
                type='kv_cache_update_paged',
                inputs={'Cache': [cache], 'New': [new],
                        'Positions': [pos], 'BlockTables': [table]},
                outputs={'Out': [cache]},
                attrs=dict(ring, layer=int(layer),
                           block_size=int(block_size)))

    def attend(q, layer, name, kind):
        if cfg.attention == 'mla':
            return _mla_attend(cfg, layers.mla_decode_attention, q, kc,
                               pos, btab, layer)
        caches, table, bound = window + ({'window': cfg.sliding_window},) \
            if kind == 'window' else ((kc, vc), btab, {})
        ctx = block.create_var(name=name + '.kv_ctx',
                               shape=(-1, h, dh), dtype='float32')
        block.append_op(
            type='kv_decode_attention_paged',
            inputs={'Q': [q], 'KCache': [caches[0]], 'VCache': [caches[1]],
                    'Positions': [pos], 'BlockTables': [table]},
            outputs={'Out': [ctx]},
            attrs=dict(bound, layer=layer, scale=dh ** -0.5,
                       block_size=int(block_size)))
        return ctx

    # an idle slot's table row is all zero and a live slot's first page is
    # never block 0 (the trash block): the expert loads count live rows
    valid = layers.slice(btab, axes=[1], starts=[0], ends=[1]) \
        if cfg.ffn == 'moe' or cfg.passes > 1 else None
    routing, gates = [], []
    logits = _decode_tower(cfg, x, cache_write, attend, pos=pos,
                           valid=valid, routing=routing,
                           mixers={'conv': conv, 'ssm': ssm, 'ssd': ssd,
                                   'gdn': gdn}, gates=gates)
    next_tokens = _append_sample_op(block, logits, sample_vars,
                                    'gen_next_tokens')       # [S]
    out = {'tokens': tokens, 'pos': pos, 'logits': logits,
           'next_tokens': next_tokens, 'k_cache': kc, 'v_cache': vc}
    if gates:
        out['exit_gates'] = gates
        out['tokens_and_load'] = layers.concat(
            [next_tokens, _exit_masses(gates, valid)], axis=0)
    return _expert_outputs(out, next_tokens, routing)


def build_lm_drafter(cfg, slots, max_len, spec_k, num_blocks, block_size):
    """``spec_k`` greedy decode steps UNROLLED into one compiled program
    — the draft leg of speculative decoding. Each unrolled step is the
    same `_decode_tower` as the plain decode step, its argmax feeding
    the next step's embedding IN-PROGRAM, so the K draft proposals cost
    one host dispatch instead of K (the chip never waits on the host
    between draft tokens).

    Feeds: 'gen_tokens' [slots, 1] int64 (each slot's last accepted
    token), 'gen_pos' [slots, 1] int64 (the position draft step 0
    writes; step j writes pos + j), 'gen_btab'
    [slots, max_len // block_size] int64 per-slot DRAFT block tables,
    and 'gen_vmask' [slots, spec_k + 1] int64 (nonzero = step j's write
    is budgeted; zero rows — idle slots, positions at or past max_len —
    redirect to the trash block). Returns {'tokens', 'pos',
    'block_table', 'vmask', 'draft_tokens' (list of spec_k [slots]
    int64 vars), 'k_cache', 'v_cache'}.

    The unroll is spec_k + 1 towers: the trailing step is WRITE-ONLY
    (``head=False`` — no logits), depositing the K-th draft token's own
    K/V at position pos + spec_k. Without it, a fully-accepted round
    (spec_k drafts + the target's bonus token) would leave a hole in
    the draft cache at the bonus position and every later draft would
    attend garbage there — the accept rate of a target-equal draft
    would silently drop from 1.0.

    Drafting is greedy by construction (argmax — the same
    ``jnp.argmax`` the sample op's temperature-0 branch takes): a draft
    is a PROPOSAL, the target's verify step decides every emitted
    token, so draft sampling would only lower the accept rate."""
    _name_program('lm_drafter')
    _require_classic_block(cfg, 'build_lm_drafter')
    d, h, dh = cfg.d_model, cfg.n_head, cfg.head_dim
    mb = max_len // block_size
    tokens = layers.data(name='gen_tokens', shape=[1], dtype='int64')
    pos = layers.data(name='gen_pos', shape=[1], dtype='int64')
    btab = layers.data(name='gen_btab', shape=[mb], dtype='int64')
    vmask = layers.data(name='gen_vmask', shape=[spec_k + 1],
                        dtype='int64')
    block = tokens.block
    pools = _declare_paged_kv_caches(block, cfg, num_blocks, block_size)
    kc, vc = pools[KV_CACHE_K], pools[KV_CACHE_V]
    pe = layers.assign(position_encoding_table(max_len, d))

    drafts = []
    tok = tokens                                 # [S, 1] feed; then [S]
    for j in range(spec_k + 1):
        if j == 0:
            pos_j = pos
        else:
            pos_j = layers.elementwise_add(
                pos, layers.fill_constant(shape=[1], dtype='int64',
                                          value=j))
        valid_j = layers.slice(vmask, axes=[1], starts=[j], ends=[j + 1])
        x = layers.embedding(
            tok, size=[cfg.vocab_size, d], dtype='float32',
            param_attr=ParamAttr(name='tok_emb.w'))          # [S, d]
        # jnp gather clips out-of-bounds rows, so a capped slot's
        # pos >= max_len reads the last PE row — its output is garbage
        # the host never accepts, and its cache write is vmask-trashed
        x = layers.elementwise_add(x, layers.gather(pe, pos_j))

        def cache_write(k, v, layer, _kind, _pos=pos_j, _valid=valid_j):
            for cache, new in ((kc, k), (vc, v)):
                block.append_op(
                    type='kv_cache_update_paged',
                    inputs={'Cache': [cache], 'New': [new],
                            'Positions': [_pos], 'BlockTables': [btab],
                            'Valid': [_valid]},
                    outputs={'Out': [cache]},
                    attrs={'layer': int(layer),
                           'block_size': int(block_size)})

        def attend(q, layer, name, _kind, _pos=pos_j):
            ctx = block.create_var(name=name + '.kv_ctx',
                                   shape=(-1, h, dh), dtype='float32')
            block.append_op(
                type='kv_decode_attention_paged',
                inputs={'Q': [q], 'KCache': [kc], 'VCache': [vc],
                        'Positions': [_pos], 'BlockTables': [btab]},
                outputs={'Out': [ctx]},
                attrs={'layer': layer, 'scale': dh ** -0.5,
                       'block_size': int(block_size)})
            return ctx

        logits = _decode_tower(cfg, x, cache_write, attend,
                               tag='.draft%d' % j,
                               head=j < spec_k)              # [S, V]
        if j < spec_k:
            tok = layers.argmax(logits, axis=1)              # [S] int64
            drafts.append(tok)
    # ONE [S, spec_k] fetch: K separate fetches would cost K host
    # syncs per round (syscall-priced in this sandbox)
    cat = layers.concat([layers.reshape(t, shape=[-1, 1])
                         for t in drafts], axis=1)
    return {'tokens': tokens, 'pos': pos, 'block_table': btab,
            'vmask': vmask, 'draft_tokens': cat,
            'k_cache': kc, 'v_cache': vc}


def build_lm_verify(cfg, slots, width, max_len, num_blocks, block_size):
    """Target-model VERIFY step: score ``width = spec_k + 1`` positions
    of every slot in ONE batched dispatch — the wide sibling of the
    decode step that converts K sequential target steps into one.

    Row t of slot s carries the token at global position
    ``gen_pos[s, t]`` (row 0 = the slot's last accepted token, rows
    1..K = the draft proposals). Every row's K/V is deposited through
    the slot's block table first (`kv_cache_update_span_paged`,
    vmask-guarded), then each row attends the cached history plus the
    window rows at or before it (`kv_verify_attention_paged`) — so row
    t's logits are IDENTICAL to what the plain decode step would have
    produced at that position, and the greedy argmax over them is the
    bitwise acceptance oracle: tokens are emitted exactly as
    non-speculative greedy decode would have emitted them, speculation
    only changes how many land per dispatch.

    The program IS the decode tower: the (slot, window-row) pairs
    flatten onto the tower's row axis ([slots * width, d]) and run the
    SAME `_decode_tower` body as the plain decode step and the drafter
    — only the cache write (span variant) and attention (per-row
    position masks) closures differ, so the acceptance oracle can
    never numerically drift from the step program it stands in for.

    Feeds: 'gen_tokens' [slots, width] int64, 'gen_pos' [slots, width]
    int64 (host-clipped to max_len - 1), 'gen_btab'
    [slots, max_len // block_size] int64, 'gen_vmask' [slots, width]
    int64. Returns {'tokens', 'pos', 'block_table', 'vmask', 'logits'
    ([slots * width, vocab], row-major), 'verify_tokens'
    ([slots * width] int64, row-major), 'k_cache', 'v_cache'}."""
    _name_program('lm_verify')
    _require_classic_block(cfg, 'build_lm_verify')
    d, h, dh = cfg.d_model, cfg.n_head, cfg.head_dim
    W = int(width)
    if W < 2:
        raise ValueError("verify width must be >= 2 (spec_k >= 1), "
                         "got %d" % W)
    mb = max_len // block_size
    tokens = layers.data(name='gen_tokens', shape=[W], dtype='int64')
    pos = layers.data(name='gen_pos', shape=[W], dtype='int64')
    btab = layers.data(name='gen_btab', shape=[mb], dtype='int64')
    vmask = layers.data(name='gen_vmask', shape=[W], dtype='int64')
    block = tokens.block
    pools = _declare_paged_kv_caches(block, cfg, num_blocks, block_size)
    kc, vc = pools[KV_CACHE_K], pools[KV_CACHE_V]

    flat = layers.reshape(tokens, shape=[-1])                # [S*W]
    x = layers.embedding(
        flat, size=[cfg.vocab_size, d], dtype='float32',
        param_attr=ParamAttr(name='tok_emb.w'))              # [S*W, d]
    pe = layers.assign(position_encoding_table(max_len, d))
    x = layers.elementwise_add(x, layers.gather(pe, pos))

    def cache_write(k, v, layer, _kind):
        # tower rows [S*W, H, dh] -> the span op's [S, H, W, dh]
        for cache, new in ((kc, k), (vc, v)):
            rows = layers.transpose(
                layers.reshape(new, shape=[-1, W, h, dh]),
                perm=[0, 2, 1, 3])
            block.append_op(
                type='kv_cache_update_span_paged',
                inputs={'Cache': [cache], 'New': [rows],
                        'Positions': [pos], 'BlockTables': [btab],
                        'Valid': [vmask]},
                outputs={'Out': [cache]},
                attrs={'layer': int(layer),
                       'block_size': int(block_size)})

    def attend(q, layer, name, _kind):
        qw = layers.transpose(layers.reshape(q, shape=[-1, W, h, dh]),
                              perm=[0, 2, 1, 3])             # [S,H,W,dh]
        ctx = block.create_var(name=name + '.verify_attn_out',
                               shape=(-1, h, W, dh), dtype='float32')
        block.append_op(
            type='kv_verify_attention_paged',
            inputs={'Q': [qw], 'KCache': [kc], 'VCache': [vc],
                    'Positions': [pos], 'BlockTables': [btab]},
            outputs={'Out': [ctx]},
            attrs={'layer': layer, 'scale': dh ** -0.5,
                   'block_size': int(block_size)})
        # [S, W, H, dh]: the tower's reshape([-1, d]) then folds the
        # heads back into row order (s, w)
        return layers.transpose(ctx, perm=[0, 2, 1, 3])

    logits = _decode_tower(cfg, x, cache_write, attend,
                           tag='.verify')                    # [S*W, V]
    # the same jnp.argmax the sample op's temperature-0 branch takes —
    # greedy acceptance is bitwise against the plain decode step
    nxt = layers.argmax(logits, axis=1)                      # [S*W]
    return {'tokens': tokens, 'pos': pos, 'block_table': btab,
            'vmask': vmask, 'logits': logits, 'verify_tokens': nxt,
            'k_cache': kc, 'v_cache': vc}


def build_lm_prefill_paged(cfg, prompt_len, num_blocks, block_size,
                           max_blocks, slots=None, shared=False):
    """Prefill one prompt SUFFIX (padded to the `prompt_len` bucket) into
    a paged cache slot and emit the first generated token (`slots`,
    `shared`: as the decode step's, for the pools the slots size).

    The suffix's query row t sits at global position ctx_len + t: with a
    shared prefix of ctx_len tokens already cached in the slot's leading
    block-table entries, only the suffix is embedded, projected and
    written — the prefix K/V are READ by `kv_prefix_attention`, never
    recomputed, which is exactly the prefill-compute saving prefix
    sharing promises. ctx_len = 0 degenerates to the ordinary causal
    prefill (computed against the cache instead of a local K/V copy).

    Feeds: 'gen_prompt' [1, prompt_len] int64 (suffix tokens),
    'gen_pos' [1, prompt_len] int64 (global positions ctx_len + t,
    host-precomputed), 'gen_btab' [1, max_blocks] int64 (the slot's
    block table), 'gen_len' [1, 1] int64 (REAL suffix length; pad rows
    write to the trash block), and the `SAMPLE_FEEDS` quad [1, 1]. With
    window layers also 'gen_wtab' [1, window_ring], the slot's ring in
    those layers' pools (sized by ``slots``): such a layer attends the
    suffix's own rows and the ``sliding_window - 1`` rows its ring holds
    from before them, THEN writes — of the suffix, only the rows a later
    query can still see. With state-space layers also 'gen_srow' [1, 1],
    the slot's row in their pools: such a layer starts from zeros at
    position 0 and from the row past it, and leaves the row as of the last
    real position.
    Returns {'prompt', 'positions', 'block_table', 'length', 'logits',
    'first_token', 'k_cache', 'v_cache'}, and with experts
    'tokens_and_load' (first_token and the [n_layer * n_experts] expert
    loads of the REAL suffix rows, one int64 vector: fetch it instead)
    and 'topk_idx' (`_expert_outputs`); with `LMConfig.passes` the stack
    runs that many times, `_close_pass` after each (no gate is read: the
    exit masses are a decode step's)."""
    _name_program('lm_prefill_paged', cfg)
    d, h, dh = cfg.d_model, cfg.n_head, cfg.head_dim
    T = int(prompt_len)
    prompt = layers.data(name='gen_prompt', shape=[-1, T], dtype='int64')
    pos = layers.data(name='gen_pos', shape=[-1, T], dtype='int64')
    btab = layers.data(name='gen_btab', shape=[max_blocks], dtype='int64')
    length = layers.data(name='gen_len', shape=[1], dtype='int64')
    sample_vars = _sampling_inputs()
    block = prompt.block
    feeds = _slot_feeds(cfg, block_size)
    wtab = feeds.get('ring')
    pools = _declare_paged_kv_caches(block, cfg, num_blocks, block_size,
                                     slots, shared)
    kc, vc = pools[KV_CACHE_K], pools.get(KV_CACHE_V)
    wkc, wvc = pools.get(WINDOW_CACHE_K), pools.get(WINDOW_CACHE_V)

    x = layers.embedding(
        prompt, size=[cfg.vocab_size, d], dtype='float32',
        param_attr=ParamAttr(name='tok_emb.w'))              # [1, T, d]
    if cfg.position == 'sinusoid':
        # decode-parity positioning: gather the SAME sinusoid table rows
        # the decode step gathers, at the suffix's global positions
        pe = layers.assign(position_encoding_table(
            max_blocks * block_size, d))
        pe_rows = layers.reshape(layers.gather(pe, pos), shape=[-1, T, d])
        x = layers.elementwise_add(x, pe_rows)

    def cache_write(cache, new, layer, table=btab, **bound):
        block.append_op(
            type='kv_cache_prefill_paged',
            inputs={'Cache': [cache], 'New': [new], 'Positions': [pos],
                    'BlockTable': [table], 'Length': [length]},
            outputs={'Out': [cache]},
            attrs=dict(bound, layer=int(layer), block_size=int(block_size)))

    def conv(g, weight_attr, layer):
        return layers.short_conv_prefill(
            g, pools[CONV_CACHE], pos, btab, length, layer, block_size,
            cfg.conv_kernel, param_attr=weight_attr)

    def ssm(u, z, prefix, layer):
        return layers.ssm_prefill(
            u, z, pools[SSM_STATE], pools[SSM_TAIL], feeds['row'], pos,
            length, layer, prefix, cfg.ssm_state, cfg.ssm_conv,
            cfg.ssm_dt_rank, epsilon=cfg.rms_eps)

    def ssd(xbc, z, dt, prefix, layer):
        return layers.ssd_prefill(
            xbc, z, dt, pools[SSD_STATE], pools[SSD_TAIL], feeds['row'],
            pos, length, layer, prefix, cfg.ssm_groups, cfg.ssm_conv,
            cfg.ssm_chunk, epsilon=cfg.rms_eps)

    def gdn(qkv, z, b, a, prefix, layer):
        return layers.gdn_prefill(
            qkv, z, b, a, pools[GDN_STATE], pools[GDN_TAIL], feeds['row'],
            pos, length, layer, prefix, cfg.gdn_key_heads, cfg.ssm_conv,
            cfg.gdn_chunk, epsilon=cfg.rms_eps,
            allow_neg_eigval=cfg.gdn_allow_neg_eigval)

    def attention(ln1, p, nth, layer, tag):
        """An attention layer's mixer: q, k, v, the cache writes, the
        suffix's attention against the slot's pages, the projection
        (`tag`: the pass, in the one intermediate that is named)."""
        q, k, v, gate = _qkv(cfg, ln1, p, pos, T, layer=layer)  # [1,H,T,dh]
        window = cfg.layer_types[layer] == 'window'
        if not window:
            cache_write(kc, k, nth)
        if cfg.attention == 'mla':
            ctx = _mla_attend(cfg, layers.mla_prefix_attention, q, kc, pos,
                              btab, nth)                     # [1,T,H,v]
        else:
            ins, bound = {'KCache': [kc], 'VCache': [vc],
                          'BlockTable': [btab]}, {}
            if window:
                # the ring cannot hold the suffix: its own K and V go into
                # the attention as they are, beside the rows the ring holds
                # from before them, and are written behind it
                ins = {'KCache': [wkc], 'VCache': [wvc], 'K': [k], 'V': [v],
                       'BlockTable': [wtab], 'Length': [length]}
                bound = {'window': cfg.sliding_window}
            else:
                cache_write(vc, v, nth)
            ctx = block.create_var(name=p + tag + '.prefix_attn_out',
                                   shape=(-1, h, T, dh), dtype='float32')
            block.append_op(
                type='kv_prefix_attention',
                inputs=dict(ins, Q=[q], Positions=[pos]),
                outputs={'Out': [ctx]},
                attrs=dict(bound, layer=nth, scale=dh ** -0.5,
                           block_size=int(block_size)))
            if window:
                cache_write(wkc, k, nth, wtab, **bound)
                cache_write(wvc, v, nth, wtab, **bound)
            ctx = layers.transpose(ctx, perm=[0, 2, 1, 3])
        ctx = _gated(layers.reshape(ctx, shape=[0, T, cfg.attn_width]),
                     gate)
        return layers.fc(ctx, size=d, num_flatten_dims=2,
                         param_attr=ParamAttr(name=p + '.attn.proj.w'),
                         bias_attr=_bias(cfg, p + '.attn.proj.b'))

    delta = None
    routing = []
    mixers = {'conv': conv, 'ssm': ssm, 'ssd': ssd, 'gdn': gdn}
    for t in range(cfg.passes):
        with _loop_pass(cfg, block, t) as tag:
            for i in range(cfg.n_layer):
                p = 'layer_%d' % i
                nth, kind = cfg.cache_ordinal(i, t), cfg.layer_types[i]
                if cfg.has_mixer(i):
                    ln1, x = _norm(cfg, x, delta, 2, p + '.ln1')
                    delta = _MIXERS[kind](cfg, ln1, p, nth, mixers[kind],
                                          2) if kind in _MIXERS \
                        else attention(ln1, p, nth, i, tag)
                    delta = _out_norm(cfg, delta, 2, p + '.ln1')
                if cfg.has_ffn(i):
                    ln2, x = _norm(cfg, x, delta, 2, p + '.ln2')
                    delta, routed = _ffn(cfg, ln2, p, 2, length=length,
                                         layer=i)
                    delta = _out_norm(cfg, delta, 2, p + '.ln2')
                    if routed is not None:
                        routing.append(routed)
            x, delta = _close_pass(cfg, x, delta, 2, None), None
    x_flat = layers.reshape(x, shape=[-1, d])                # [T, d]
    one = layers.fill_constant(shape=[1], dtype='int64', value=1)
    last = layers.gather(x_flat, layers.elementwise_sub(length, one))
    logits = _lm_head(cfg, last)                             # [1, V]
    first_token = _append_sample_op(block, logits, sample_vars,
                                    'gen_first_token')       # [1]
    return _expert_outputs(
        {'prompt': prompt, 'positions': pos, 'block_table': btab,
         'length': length, 'logits': logits, 'first_token': first_token,
         'k_cache': kc, 'v_cache': vc}, first_token, routing)
