"""The held experts' matmuls' share of their roofline: the least time the chip
could take for the six grouped matmuls a routed layer runs a step, over the
rows the program counted in the traced steps (how many assignments land on
the held experts is data; ``flops_mla.held_expert_matmul_cost``), over the
device time under the scope ``experts``, first chip. The scope also holds the
activation and the weights' casts, which lowers this share."""
from perfbench.harness import flops, flops_mla, mla_scopes

LAYER, UNIT, MOVES = "kernels", "%", "tokens_per_s_per_chip"


def read(trace, run):
    took = mla_scopes.seconds(trace, run, "mlp", ("experts",))
    if run["peaks"] is None or not took or not run.get("held_rows_traced"):
        return None
    layer_steps = (run["traced_steps"] * run["workload"]["grad_accum"]
                   * run["shape"]["moe_layers"])
    least, bound = flops.roofline_seconds(
        *flops_mla.held_expert_matmul_cost(run["shape"], run["held_rows_traced"], layer_steps),
        run["peaks"])
    print(f"perfbench: held experts' matmuls are {bound}-bound; least {least:.4f} s over "
          f"{run['held_rows_traced']:.0f} counted rows, took {took:.4f} s over the traced "
          f"steps", flush=True)
    return 100.0 * least / took
