"""agent_fire_share: the share of the epochs the learned lanes' programs
scanned in which the batch's DQN step ran (the program's any-lane-invokes
agent branch was taken), in percent, over the calls of the window.  Read
from the program's counters (`SweepResult.counters`); a program without
them gives nothing."""


def read(rec: dict):
    fires, epochs = rec.get("agent_fires"), rec.get("agent_epochs")
    if not epochs:
        return None
    return 100.0 * fires / epochs
