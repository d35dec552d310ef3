"""Masked tokens over data tokens, mean over the traced steps, in percent: the
share of a document's tokens the loss is taken on. From the program's counter
(the train step returns the masked-token count with its loss), not from the
trace. t uniform on [0.001, 1] a block gives 50 on average; a step's own
reading moves with its noise, which moves the loss's scale, not the step's
time."""
LAYER, UNIT, MOVES = "model", "%", "tokens_per_s_per_chip"


def read(trace, run):
    return run.get("bd_masked_share_pct")
