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
# The third level (``parallel.round.PIECES``), inside a layer, set where the
# work happens: the four parts of a state-space mixer around its scan, the
# attention core alone inside ``attention`` (whichever body of
# ``packed_attention.attention_core`` runs; the rest of ``attention`` is the
# projections'), the Sinkhorn iterations alone inside ``hyper_conn``, the
# low-rank projections of latent attention (their norms and RoPE) beside the
# core, and the four parts of a KDA mixer around its scan (the input
# projections, the three short convolutions, the decay / step / norms /
# output gate, the output projection).
(SSM_IN_PROJ, SSM_CONV, SSM_GATE_NORM, SSM_OUT_PROJ, ATTN_CORE, HC_SINKHORN,
 ATTN_LATENT, KDA_IN_PROJ, KDA_CONV, KDA_GATES, KDA_OUT_PROJ) = PIECES = (
    "ssm_in_proj", "ssm_conv", "ssm_gate_norm", "ssm_out_proj", "attn_core",
    "hc_sinkhorn", "attn_latent", "kda_in_proj", "kda_conv", "kda_gates",
    "kda_out_proj")
# An outer scope AROUND layers: a whole multi-token-prediction module, whose
# attention, experts and head keep their own layers' names inside it
# (``parallel.round.MODULES``).
MTP = "mtp"
MODULES = (MTP,)
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
