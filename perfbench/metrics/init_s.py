"""Wall seconds to make the seeded weights, optimizer state and token table
on the device (``create_train_state`` to ``block_until_ready``)."""
LAYER, UNIT, MOVES = "set-up", "s", "setup_s"


def read(trace, run):
    return run["init_s"]
