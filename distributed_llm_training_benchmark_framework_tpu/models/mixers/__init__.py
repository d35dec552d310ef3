"""A layer kind has one owner: the table from a kind of layer
(``TinyGPTConfig.layer_types``) to the module that mixes it. Whatever asks
"what does a layer of this kind need, hold, run, count or keep?" asks here;
no other module compares a kind with ``kda``, ``ssd`` or ``conv``. A mixer is
a plain module with the same few names:

- ``NEEDS``, ``check(c)``: what the layer needs of its own config fields, and
  whether the config gives it (``check`` below adds the clause they share);
- ``STACKS``: its stacks of the parameter tree where it has names of its own,
  in the order ``init_params`` draws them (the seeds' contract);
- ``leaves(c, k, L, kind)``: one stack's norm scales and mixer leaves, L
  layers, drawn from the key iterator ``k``; ``AXIS_RULES``: their logical
  axes (the norms' and the feed-forward part's are ``tinygpt``'s);
- ``sublayer(c, x, layer, key, deterministic, kind, qk_tables)``: norm ->
  mixer -> residual under the scopes of ``utils/scopes.py`` that it opens
  (the three that need no key, kind or table take and ignore them);
- ``RESIDUAL_NAMES``, ``CAST_NAMES``: the ``checkpoint_name``s of its kernels'
  results and of its wide products after their casts (``tinygpt._under_remat``
  has the rule for both lists);
- ``forward_flops_per_token(c, kind)`` (``utils/flops.py`` sums them) and
  ``kept_bytes(c, pol, S, cbytes)``: what a layer keeps of a sequence under a
  remat policy beyond ``utils.memory.estimate_hbm``'s coefficients;

and its own counters (``kda_stats``, ``ssd_stats``, ``sconv_stats``;
attention's three). Adding a kind: its module here, its kernels' file under
``ops/``, its fields on ``TinyGPTConfig``, its scope names in
``utils/scopes.py``, one line of ``MIXERS`` (and of ``MATMUL_CAST_NAMES``).
"""

from ...utils import scopes
from ..common import MLP_GU, SHARED_U
from . import attention, conv, kda, ssd

#: kind of layer -> its mixer. ``mlp`` is no mixer (``LAYER_KINDS``).
MIXERS = {
    scopes.GLOBAL: attention,
    scopes.WINDOW: attention,
    scopes.KDA: kda,
    scopes.SSD: ssd,
    scopes.CONV: conv,
}
MODULES = tuple(dict.fromkeys(MIXERS.values()))

#: Every module's, in the table's order.
AXIS_RULES = {leaf: axes for module in MODULES for leaf, axes in module.AXIS_RULES.items()}
STACKS = tuple(name for module in MODULES for name in module.STACKS)
RESIDUAL_NAMES = tuple(name for module in MODULES for name in module.RESIDUAL_NAMES)

#: The wide products named after their casts, the mixers' and the feed-forward part's, in
#: the order the list grew (nothing reads it: a policy takes a set); a new mixer's go last.
MATMUL_CAST_NAMES = (*kda.CAST_NAMES, MLP_GU, *ssd.CAST_NAMES, SHARED_U, *conv.CAST_NAMES)


def of(kind):
    """The module that mixes a layer of ``kind`` (None: a stack of one kind)."""
    return attention if kind is None else MIXERS[kind]


def own_leaves(kinds) -> bool:
    """Whether a layer of ``kinds`` has a mixer whose leaves are not attention's."""
    return any(MIXERS.get(kind, attention) is not attention for kind in kinds or ())


def stack_name(kind, dense: bool) -> str:
    """The stack of a layer of ``kind`` where stacks go by mixer and MLP (a leading dense one)."""
    return next(name for name in of(kind).STACKS if name.endswith("dense_blocks") == dense)


def check(c) -> None:
    """Refuse a config whose ``layer_types`` name a mixer it cannot run: each
    module's own fields (``NEEDS``) and the clause every mixer but attention
    shares: stacks of leaves of their own run unrolled (``tinygpt.
    _apply_stacks``), under RMSNorm without bias, dropout or collective matmul."""
    shared = (c.norm == "rmsnorm" and not c.bias and not c.dropout
              and not c.tp_collective_matmul and not c.scan_layers)
    for kind in dict.fromkeys(c.layer_types):
        module = MIXERS.get(kind, attention)
        if module is not attention and not (shared and module.check(c)):
            raise ValueError(
                f"{module.NEEDS}, norm='rmsnorm', bias=False, no dropout, no "
                "tp_collective_matmul and scan_layers=False: stacks of unequal leaves run "
                "unrolled, in the published order, and the scanned loop is refused")
