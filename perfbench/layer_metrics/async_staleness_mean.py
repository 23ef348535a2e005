"""Mean over the window of the program's `hist["staleness"]`: updates
published between a worker's read of the parameters and the use of its
gradient.  A count made by the program."""


def read(obs):
    v = obs["result"].get("staleness") or []
    return sum(v) / len(v) if v else None
