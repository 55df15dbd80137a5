"""Every ``jax.named_scope`` a model opens, once.

What ``analysis.program.program_scopes`` puts a compiled program's operations
down to under the round's stages, and what the benchmark's per-layer metrics
are keyed on: a string here is a name in a trace. Models and pieces open
scopes with these constants and no literal; ``fedtpu.parallel.round`` builds
``LAYERS`` and ``PIECES`` from the tuples below plus its own two. A new model
appends its names here; any model may open any of them.
"""

from __future__ import annotations

# The second level, under the stages: the parts of a model
# (``parallel.round.LAYERS``).
EMBED, ATTENTION, ROUTER, EXPERT_DISPATCH, EXPERTS, LM_HEAD_LOSS = (
    "embed", "attention", "router", "expert_dispatch", "experts",
    "lm_head_loss")
# the hybrid stack's own: a state-space mixer, the chunked scan alone inside
# it (innermost), the expert every token takes
SSM, SSM_SCAN, SHARED_EXPERT = "ssm", "ssm_scan", "shared_expert"
# the four-stream stack's own: the mixing of the residual streams around
# every sublayer, a plain gated MLP layer, and the projection that opens a
# multi-token-prediction module
HYPER_CONN, DENSE_MLP, MTP_PROJ = "hyper_conn", "dense_mlp", "mtp_proj"
# the delta-rule stack's own: a KDA mixer, and the chunked recurrence alone
# inside it (innermost)
KDA, KDA_SCAN = "kda", "kda_scan"
LAYERS = (EMBED, ATTENTION, ROUTER, EXPERT_DISPATCH, EXPERTS, LM_HEAD_LOSS,
          SSM, SSM_SCAN, SHARED_EXPERT, HYPER_CONN, DENSE_MLP, MTP_PROJ, KDA,
          KDA_SCAN)
# The decoder-hybrid-decoder stack names no layer of its own: its Mamba-1
# mixers and the Gated Memory Unit that reads their memory are the
# state-space path (``ssm``), its three attentions ``attention``, its
# feed-forward ``dense_mlp``; what is its own lies a level below and a level
# above (``PIECES``, ``MODULES``).
# The third level (``parallel.round.PIECES``), inside a layer, set where the
# work happens: the four parts of a state-space mixer around its scan, the
# attention core alone inside ``attention`` (whichever body of
# ``packed_attention.attention_core`` runs; the rest of ``attention`` is the
# projections'), the Sinkhorn iterations alone inside ``hyper_conn``, the
# low-rank projections of latent attention (their norms and RoPE) beside the
# core, the four parts of a KDA mixer around its scan (the input
# projections, the three short convolutions, the decay / step / norms /
# output gate, the output projection), the four parts of a Mamba-1 (S6)
# mixer (its four projections, the short convolution, the selective scan,
# the ``D`` skip and the gate) and the Gated Memory Unit inside ``ssm``, the
# combination of differential attention's two softmaxes inside ``attention``,
# the meeting of a tied embedding's two gradients inside ``embed``, and the
# sigmoid gate on a grouped-query layer's context (its projection, the
# sigmoid, the product) inside ``attention``.
(SSM_IN_PROJ, SSM_CONV, SSM_GATE_NORM, SSM_OUT_PROJ, ATTN_CORE, HC_SINKHORN,
 ATTN_LATENT, KDA_IN_PROJ, KDA_CONV, KDA_GATES, KDA_OUT_PROJ, S6_PROJ,
 S6_CONV, S6_SCAN, S6_GATE, GMU, DIFF_COMBINE, TIED_EMBED_GRAD,
 ATTN_GATE) = PIECES = (
    "ssm_in_proj", "ssm_conv", "ssm_gate_norm", "ssm_out_proj", "attn_core",
    "hc_sinkhorn", "attn_latent", "kda_in_proj", "kda_conv", "kda_gates",
    "kda_out_proj", "s6_proj", "s6_conv", "s6_scan", "s6_gate", "gmu",
    "diff_combine", "tied_embed_grad", "attn_gate")
# An outer scope AROUND layers: a whole multi-token-prediction module, whose
# attention, experts and head keep their own layers' names inside it; and
# which of three an attention layer is (under a window, full, or reading
# another layer's keys and values), whose projections, core and combination
# keep ``attention``'s names inside it (``parallel.round.MODULES``).
MTP, ATTN_WINDOW, ATTN_FULL, ATTN_CROSS = MODULES = (
    "mtp", "attn_window", "attn_full", "attn_cross")
# Not a piece but a direction: a forward pass run again by hand inside a
# backward rule (the held experts') names itself so, as remat's lowering
# names its own (``parallel.round.RECOMPUTE``).
RECOMPUTE = "recompute"
# Kernels the TPU's compiler puts in an instruction's place under a name of
# its own, which replaces the ``op_name`` and with it every scope: whose they
# are, by the prefix of the instruction's name. ``lax.ragged_dot`` becomes
# ``ragged-dot-none*`` (and one ``ragged-dot-metadata`` a call), and the only
# grouped matmuls of a program are its experts'. (The Pallas body of
# ``ops.grouped_matmul.grouped_matmul`` needs no entry: a Mosaic call keeps
# its op_name.)
LAYER_KERNELS = {"ragged-dot": EXPERTS}
