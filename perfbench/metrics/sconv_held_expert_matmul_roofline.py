"""``held_expert_matmul_roofline``'s arithmetic for this stack: the least time
the chip could take for the six grouped matmuls a routed layer runs a step, over
the rows the program counted in the traced steps
(``flops_mla.held_expert_matmul_cost``: 6 D F a row forward, SwiGLU), over the
device time under the scope ``experts`` as ``sconv_scopes.py`` finds it, first
chip. The scope also holds the activation and the weights' casts, which lowers
this share."""
from perfbench.harness import flops, flops_lfm2, sconv_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    found = sconv_scopes.found(trace, run)
    if (run.get("peaks") is None or found is None or not found["experts"]
            or not run.get("held_rows_traced")):
        return None
    layer_steps = run["traced_steps"] * run["workload"]["grad_accum"] * run["shape"]["moe_layers"]
    least, bound = flops.roofline_seconds(
        *flops_lfm2.held_expert_matmul_cost(
            run["shape"], run["held_rows_traced"], layer_steps), run["peaks"])
    print(f"perfbench: held experts' matmuls are {bound}-bound; least {least:.4f} s over "
          f"{run['held_rows_traced']:.0f} counted rows, took {found['experts']:.4f} s over the "
          f"traced steps", flush=True)
    return 100.0 * least / found["experts"]
