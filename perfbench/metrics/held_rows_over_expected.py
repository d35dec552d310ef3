"""Routed rows that landed on the experts this chip holds, over the tokens x
experts a token x held / experts that uniform routing gives, mean over the
routed layers and the traced steps. 1.0 is the expected load; the buffer holds
``held_rows_factor`` times it, and the run is not correct if a row did not
fit. From the program's counter (the train step returns it with its loss),
not from the trace."""
LAYER, UNIT, MOVES = "model", "ratio", "tokens_per_s_per_chip"


def read(trace, run):
    return run.get("held_rows_over_expected")
