"""The names the model and the train step put into a profile.

Each is a ``jax.named_scope``: it writes a path component into the
``op_name`` metadata of every HLO instruction traced under it and costs
nothing at run time. What forms a name takes in ``op_name`` after
differentiation and remat, and how a trace is read by them, is in
docs/OBSERVABILITY.md ("Names in a profile").
"""

EMBED, ATTENTION, MLP, DROPOUT, HEAD, LOSS, OPTIMIZER = SCOPES = (
    "embed", "attention", "mlp", "dropout", "head", "loss", "optimizer",
)

# Inside ``mlp``, where the MLP is the dropless routed layer (models/moe.py).
# A tuple of their own: a dense step has none of them, and ``SCOPES`` is what
# every step must show.
ROUTER, DISPATCH, EXPERTS, COMBINE = MOE_SCOPES = (
    "router", "dispatch", "experts", "combine",
)

# ``shared``: the shared experts, beside the four above where the config has
# them (DeepSeek-class layers).
SHARED = "shared"

# Inside ``attention``, where it is latent attention (models/tinygpt.py
# ``_latent_attention``): the projections with the latent's norm and rotary,
# the attention itself (the flash kernels), the output projection.
MLA_PROJ, MLA_CORE, MLA_OUT = MLA_SCOPES = ("mla_proj", "mla_core", "mla_out")
