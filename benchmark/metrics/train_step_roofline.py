"""Op kernels (ops/): the least time one training step could take on the
cell's chips — the larger of required FLOPs over the bf16 peak and least
bytes over the HBM peak, both from benchmark/flops/ — over the device-busy
time per traced step.  Which bound sets the floor is logged."""


def read(facts):
    trace, steps = facts.get("trace"), facts.get("traced_steps")
    if not trace or not steps or not facts.get("step_floor_s"):
        return None
    return 100.0 * facts["step_floor_s"] / (trace["busy_s"] / steps)
