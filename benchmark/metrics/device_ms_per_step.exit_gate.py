"""Op kernels (ops/loss.py): device milliseconds per traced step of a looped
model's exit-gated loss — the gate's product and sigmoid, the exit
distribution, the weighting of the passes' per-token losses, the entropy
term (``exit_gate_loss``) and their gradients (``exit_gate_loss_grad``)."""

from benchmark.metrics import _types


def read(facts):
    s = _types.seconds_per_step(facts, ("exit_gate_loss",))
    return None if s is None else s * 1e3
