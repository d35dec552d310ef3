"""The names the model and the train step put into a profile.

The device's half: each is a ``jax.named_scope``. It writes a path component
into the ``op_name`` metadata of every HLO instruction traced under it and
costs nothing at run time. What forms a name takes in ``op_name`` after
differentiation and remat, and how a trace is read by them, is in
docs/OBSERVABILITY.md ("Names in a profile").

The host's half, below it: five spans round the work ``train/step.py`` does on
the host, jax's own compile and cache events, and the collector's pauses, kept
in the process for whoever asks (``host_records``, ``compile_events``).

The step's memory, last: what ``aot_compile`` read off the step it compiled,
the devices' limit, and a callable that lists what the forward keeps for the
backward when someone asks (``step_memory``; docs/OBSERVABILITY.md, "The
step's memory"). Nothing here prints or writes a file.
"""

import collections
import gc
import time

import jax


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

# Inside ``attention``, where it is latent attention (models/mixers/attention.py
# ``_latent_attention``): the projections with the latent's norm and rotary,
# the attention itself (the flash kernels), the output projection.
MLA_PROJ, MLA_CORE, MLA_OUT = MLA_SCOPES = ("mla_proj", "mla_core", "mla_out")

# Inside ``attention``, where the stack mixes kinds of layer
# (``TinyGPTConfig.layer_types``): a layer's whole mixer sublayer under its
# kind's name, a sliding-window layer, a global one (softmax attention over
# every earlier position, latent attention too), a KDA one (the gated
# delta-rule recurrence, ``ops/kda.py``), an SSD one (a Mamba-2 mixer: the
# scalar-decay state-space scan, ``ops/ssd.py``) or a conv one (a gated short
# convolution and nothing else, ``ops/short_conv.py::gated_conv``): ``models/mixers/``.
WINDOW, GLOBAL, KDA, SSD, CONV = LAYER_KIND_SCOPES = ("window", "global", "kda", "ssd", "conv")

# Inside ``attention`` / ``kda`` (``models/mixers/kda.py::sublayer``): the
# projections with their convolutions, SiLU, l2norm, the decay and beta; the
# recurrence itself (the Mosaic calls ``kda_fwd`` / ``kda_bwd``); the head
# norm, the gate and the output projection.
KDA_PREP, KDA_CORE, KDA_OUT = KDA_SCOPES = ("kda_prep", "kda_core", "kda_out")

# Inside ``attention`` / ``ssd`` (``models/mixers/ssd.py::sublayer``): what
# stands before the scan (``in_proj``'s three products, the convolution with
# its bias and SiLU, the Mosaic calls ``kda_conv_fwd`` / ``kda_conv_bwd``, and
# dt's softplus with the log-decay); the scan itself (``ssd_fwd`` / ``ssd_bwd``
# and the running sums in front of them); the skip, the gated grouped norm and
# ``out_proj``.
SSD_PREP, SSD_CORE, SSD_OUT = SSD_SCOPES = ("ssd_prep", "ssd_core", "ssd_out")

# Inside ``attention`` / ``conv`` (``models/mixers/conv.py::sublayer``): the
# input projection to B | C | x~; the gated convolution C * conv(B * x~) (the
# Mosaic calls ``sconv_fwd`` / ``sconv_bwd``, or the ``jnp`` chain); the output
# projection.
SCONV_IN, SCONV_CORE, SCONV_OUT = SCONV_SCOPES = ("sconv_in", "sconv_core", "sconv_out")

# Inside ``attention`` (below the kind's scope where there is one), where a
# layer's QK-norm and rotary are ``ops/rotary.py``'s one pass: the two Mosaic
# calls ``qk_prologue_fwd`` / ``qk_prologue_bwd`` and the sum of the scale
# gradients' partial sums. A layer on the ``jnp`` chain has no such scope.
QK_PROLOGUE = "qk_prologue"

# Inside ``attention`` (below the kind's scope where there is one), where the
# config has ``attn_gate``: the gate's projection, its sigmoid and the product
# with the kernel's (B, S, H, head_dim) result, before ``wo``.
ATTN_GATE = "attn_gate"

# Inside ``embed``, under block diffusion (models/tinygpt.py ``bd_stream``):
# drawing a noise level a block, masking, and joining the noisy copy to the
# clean one.
NOISE = "noise"

# Every name above: what a scope path read back from a name stack is held to
# (``utils/residuals.py::scope_path``).
NAMES = frozenset((*SCOPES, *MOE_SCOPES, SHARED, *MLA_SCOPES, *LAYER_KIND_SCOPES, *KDA_SCOPES,
                   *SSD_SCOPES, *SCONV_SCOPES,
                   QK_PROLOGUE, ATTN_GATE, NOISE))


# ---------------------------------------------------------------------------
# The host's half: what the Python side of a run was doing, kept in the
# process. A device name above is metadata in the compiled step; a host name
# is a span on the host's clock. Both halves are read by name
# (docs/OBSERVABILITY.md, "Names in a profile", second table).
# ---------------------------------------------------------------------------

# ``train/step.py`` opens each where the work happens. None may equal a span
# of whoever drives the step (the benchmark's runner has ``dispatch`` and
# ``loss_fetch``, found by exact name).
INIT_PARAMS, INIT_OPT_STATE, STEP_LOWER, STEP_COMPILE, STEP_DISPATCH = HOST_SPANS = (
    "init_params", "init_opt_state", "step_lower", "step_compile", "step_dispatch",
)
GC = "gc"  # a collection of Python's collector, recorded beside the spans

# (perf_counter_ns, time_ns) read together: where the program's part of set-up
# begins, and what turns a record's times (``perf_counter_ns``: monotonic, the
# clock of a driver's own ``perf_counter``) into the profiler's (the wall
# clock), see ``wall_ns``.
IMPORTED_AT = (time.perf_counter_ns(), time.time_ns())

# What jax reports of a compilation through ``jax.monitoring``, with the
# function's name: its trace, its lowering, the compiler's run (a cache read
# included, which the fourth times alone).
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
COMPILE_EVENTS = (TRACE_EVENT, LOWER_EVENT, BACKEND_COMPILE_EVENT, CACHE_READ_EVENT)
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

# Bounded: a long run keeps its newest windows. Collections have a record of
# their own because tracing a large step makes thousands of them, which would
# push the set-up's few spans out of a shared one.
_MAXLEN = 4096
_spans = collections.deque(maxlen=_MAXLEN)
_collections = collections.deque(maxlen=_MAXLEN)
_compile_sums = {}  # (event, fun_name) -> [count, seconds]
_backend_compiles = collections.deque(maxlen=_MAXLEN)  # (fun_name, end_ns, seconds)
_jit_busy = collections.deque(maxlen=_MAXLEN)  # [start_ns, end_ns], disjoint, in order
_cache = {"hits": 0, "misses": collections.deque(maxlen=_MAXLEN)}  # a miss: its time, ns
_gc_started = [0]
_step_memory = {"compiled": None, "bytes_limit": None, "saved": None}


def wall_ns(perf_ns):
    """A record's time on the wall clock, which is the profiler's."""
    return perf_ns - IMPORTED_AT[0] + IMPORTED_AT[1]


class host_span:
    """``with host_span(name, **args):`` is a ``jax.profiler.TraceAnnotation``
    (free while no profile is taken) and one ``(name, start_ns, end_ns, args)``
    in the process's record, on ``time.perf_counter_ns``. It reads two clocks
    and appends; it never waits for the device."""

    __slots__ = ("name", "args", "annotation", "start")

    def __init__(self, name, **args):
        self.name, self.args = name, args
        self.annotation = jax.profiler.TraceAnnotation(name, **args)

    def __enter__(self):
        self.annotation.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self.annotation.__exit__(*exc)
        _spans.append((self.name, self.start, end, self.args))


def host_records(name=None):
    """The newest records, oldest first: ``(name, start_ns, end_ns, args)`` of
    every ``host_span`` and every collection (``"gc"``, with its generation)."""
    if name == GC:
        return list(_collections)
    if name is not None:
        return [r for r in _spans if r[0] == name]
    return sorted([*_spans, *_collections], key=lambda r: r[1])


def compile_events():
    """What jax has reported since the import. ``sums``: {(event, fun_name):
    (count, seconds)} of ``COMPILE_EVENTS``, a function's seconds including
    those of the functions traced inside it; ``busy``: the disjoint
    ``(start_ns, end_ns)`` in which any of them ran, each nested event counted
    once; ``backend_compiles``: ``(fun_name, end_ns, seconds)`` of each run of
    the compiler or read from the cache; ``cache_hits``; ``cache_misses``:
    when each was written to the cache, ns."""
    return {
        "sums": {key: tuple(value) for key, value in _compile_sums.items()},
        "busy": [tuple(interval) for interval in _jit_busy],
        "backend_compiles": list(_backend_compiles),
        "cache_hits": _cache["hits"],
        "cache_misses": list(_cache["misses"]),
    }


def record_step_memory(compiled, bytes_limit, saved):
    """``train/step.py::aot_compile`` calls this once a compilation."""
    _step_memory.update(compiled=compiled, bytes_limit=bytes_limit, saved=saved)


def step_memory():
    """The memory of the newest step ``aot_compile`` compiled in the process:
    ``compiled`` (``analysis.memory_anatomy.compile_memory_fields`` of it:
    ``argument_bytes``, ``output_bytes``, ``temp_bytes``, ``alias_bytes``,
    ``peak_bytes`` of buffer assignment; None where the backend has no
    analysis), ``bytes_limit`` (the smallest the mesh's devices' allocators
    report; None where they keep none) and ``saved``, which traces when called
    and not before: ``saved()`` -> ``{"kept": [...], "all": [...], "left_out":
    ...}``, what a micro-batch's forward keeps for its backward on one chip
    under the step's remat policy and under none
    (``train/step.py::saved_for_backward``); None on a mesh that is not
    data-only. All three are None before any step was compiled."""
    return dict(_step_memory)


def _on_duration(event, seconds, fun_name="", **_):
    if event not in COMPILE_EVENTS:
        return
    end = time.perf_counter_ns()
    start = end - int(seconds * 1e9)
    total = _compile_sums.setdefault((event, fun_name), [0, 0.0])
    total[0] += 1
    total[1] += seconds
    if event == BACKEND_COMPILE_EVENT:
        _backend_compiles.append((fun_name, end, seconds))
    # An event is reported when it ends, after everything nested in it.
    while _jit_busy and _jit_busy[-1][0] >= start:
        _jit_busy.pop()
    if _jit_busy and _jit_busy[-1][1] > start:
        _jit_busy[-1][1] = end
    else:
        _jit_busy.append([start, end])


def _on_event(event, **_):
    if event == CACHE_HIT_EVENT:
        _cache["hits"] += 1
    elif event == CACHE_MISS_EVENT:
        _cache["misses"].append(time.perf_counter_ns())


def _on_collection(phase, info):
    if phase == "start":
        _gc_started[0] = time.perf_counter_ns()
    else:
        _collections.append(
            (GC, _gc_started[0], time.perf_counter_ns(), {"generation": info["generation"]})
        )


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
gc.callbacks.append(_on_collection)
