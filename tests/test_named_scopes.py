"""The names the model and the train step put into a profile (utils/scopes.py).

Each case builds a tiny step through ``create_train_state`` /
``make_train_step``, as the benchmark's ``perfbench/harness/build.py`` does,
compiles it and reads the ``op_name`` metadata of the compiled text: a scope
``x`` is a path component ``x``, ``jvp(x)`` or ``transpose(jvp(x))``; inside a
scan body or under remat it stays plain below a wrapped path
(``transpose(jvp())/while/body/x``, ``transpose(...)/checkpoint/x``), and what
remat runs a second time carries ``rematted_computation``
(docs/OBSERVABILITY.md, "Names in a profile").
"""

import dataclasses
import re

import jax
import pytest

from distributed_llm_training_benchmark_framework_tpu.models.tinygpt import TinyGPTConfig
from distributed_llm_training_benchmark_framework_tpu.parallel import get_strategy, make_mesh
from distributed_llm_training_benchmark_framework_tpu.parallel.strategies import make_optimizer
from distributed_llm_training_benchmark_framework_tpu.train.step import (
    create_train_state,
    make_train_step,
)
from distributed_llm_training_benchmark_framework_tpu.utils.scopes import SCOPES

SEQ = 64
TINYGPT = dict(dropout=0.1)  # LayerNorm, learned positions, GELU, MHA, tied head
MISTRAL = dict(dropout=0.0, causal=True, norm="rmsnorm", pos_embed="rope", mlp_act="swiglu",
               mlp_hidden=96, n_kv_head=2, bias=False, tie_embeddings=False)
DIFFERENTIATED = ("attention", "mlp", "head", "loss")
WRAPPER = re.compile(r"^\w+\((.*)\)$")


def compiled_paths(knobs, scan_layers, remat="none", grad_accum=2):
    """Every ``/``-split path of every ``op_name`` of the compiled tiny step."""
    config = TinyGPTConfig(vocab_size=256, n_embd=32, n_head=4, n_layer=2, block_size=SEQ,
                           attention_impl="flash", scan_layers=scan_layers, **knobs)
    mesh = make_mesh((1, 1, 1, 1, 1), ("data", "seq", "model", "pipe", "expert"),
                     devices=jax.devices()[:1])
    strategy = dataclasses.replace(get_strategy("zero2"), remat=remat)
    shape = dict(grad_accum=grad_accum, from_table=True, global_micro=1, seq_len=SEQ)
    state = create_train_state(config, strategy, mesh, seed=0, **shape)
    _, aot_compile = make_train_step(config, strategy, make_optimizer(strategy), mesh,
                                     state.param_specs, state.opt_specs, **shape)
    table = jax.numpy.zeros((8, SEQ), jax.numpy.int32)
    text = aot_compile(state.params, state.opt_state, table).as_text()
    return [path.split("/") for op_name in re.findall(r'op_name="([^"]*)"', text)
            for path in op_name.split(";")]


def scope_of(component):
    """'transpose(jvp(attention))' -> 'attention'; a jitted function is no scope."""
    while not component.startswith("jit(") and (wrapped := WRAPPER.match(component)):
        component = wrapped.group(1)
    return component


def backward(path):
    return any(c.startswith("transpose(") for c in path)


def check(paths, dropout):
    found = {scope: [p for p in paths if scope in map(scope_of, p)] for scope in SCOPES}
    for scope in SCOPES:
        assert bool(found[scope]) == (scope != "dropout" or dropout), scope
    for scope in DIFFERENTIATED:
        assert any(backward(p) for p in found[scope]), f"no backward op under {scope}"
        assert any(not backward(p) for p in found[scope]), f"no forward op under {scope}"
    assert not any(backward(p) for p in found["optimizer"])
    for path in found["dropout"]:  # always nested in embed, attention or mlp
        names = [scope_of(c) for c in path]
        assert set(names[: names.index("dropout")]) & {"embed", "attention", "mlp"}, path


@pytest.mark.parametrize("scan_layers", [False, True], ids=["unrolled", "scan"])
@pytest.mark.parametrize("knobs", [TINYGPT, MISTRAL], ids=["tinygpt", "mistral"])
def test_every_scope_is_in_the_compiled_step(knobs, scan_layers):
    paths = compiled_paths(knobs, scan_layers)
    check(paths, dropout=knobs["dropout"] > 0)
    assert not any("rematted_computation" in p for p in paths)
    for scope in DIFFERENTIATED:
        # Unrolled, the transform wraps the scope itself; inside a scan body it
        # wraps the path above the loop and a block's scope stays plain.
        inside_scan = scan_layers and scope in ("attention", "mlp")
        for form in (f"jvp({scope})", f"transpose(jvp({scope}))"):
            assert any(form in p for p in paths) != inside_scan, form


def test_remat_marks_what_it_runs_again():
    paths = compiled_paths(MISTRAL, scan_layers=False, remat="dots", grad_accum=1)
    check(paths, dropout=False)
    again = [p for p in paths if "rematted_computation" in p]
    assert again and all(backward(p) for p in again)
    assert {scope_of(c) for p in again for c in p} & {"attention", "mlp"}
