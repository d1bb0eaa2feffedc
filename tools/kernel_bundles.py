"""Mosaic's static schedule of the streamed attention kernels over a latent
block's projections — forward and the fused backward, one block — compiled
for a DESCRIBED v5e from a CPU host: no chip, no chip time.

Prints the seconds a step program pays to trace and lower the pair and, for
each kernel, the bundles of its VLIW text with the bundles that hold a
``vmatmul`` (the MXU's floor is four times their count) or a spill — what
ranked the kernels' variants as the chip did in PRs 37, 39 and 47 (PERF.md
6; the chip runs a streamed kernel at 1.3-1.4x its bundles).  The listings
stay in ``--out`` (``*-final_bundles.txt``, one line a bundle;
``*-static-per-bundle-utilization.txt`` beside them).

Usage:
    JAX_PLATFORMS=cpu python tools/kernel_bundles.py                # train_mtp_8k's
    JAX_PLATFORMS=cpu python tools/kernel_bundles.py --t 4096 --theta none

The compile runs in a child process: libtpu aborts AFTER the dumps on a
missing html template (harmless), and one process at a time holds its lock
unless the shell sets ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``.
"""

import argparse
import glob
import os
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _compile(args):
    """The child: lower and compile one block's forward and backward, each
    under the Fluid scope a step program gives it (the dumps' names)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas import streamed_attention as sa

    place = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    n, dv = args.heads, args.v_dim
    q, kv, kr, ct = (
        jax.ShapeDtypeStruct((1, args.t, w), jnp.bfloat16, sharding=place)
        for w in (n * (args.nope + args.rope), n * (args.nope + dv),
                  args.rope, n * dv))

    def step(q, kv, kr, ct):
        with jax.named_scope("fluid[fused_attention]out"):
            out, lse = sa.forward_in_place(q, kv, kr, n, dv, args.theta, True)
        with jax.named_scope("fluid[fused_attention_grad]q.GRAD"):
            return (out,) + sa.backward_in_place(
                q, kv, kr, n, dv, out, lse, ct, args.theta, True)
    jax.config.update("jax_enable_compilation_cache", False)
    print("heads a grid step, forward / backward: %s / %s"
          % sa.in_place_step(q, kv, n, dv), flush=True)
    t0 = time.perf_counter()
    lowered = jax.jit(step).lower(q, kv, kr, ct)
    t1 = time.perf_counter()
    print("trace + lowering %.2f s" % (t1 - t0), flush=True)
    lowered.compile()             # writes the dumps; libtpu aborts after


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--t", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--nope", type=int, default=128)
    ap.add_argument("--rope", type=int, default=64)
    ap.add_argument("--v_dim", type=int, default=128)
    ap.add_argument("--theta", default=3.2e7,
                    type=lambda x: None if x == "none" else float(x))
    ap.add_argument("--out", default=None, help="directory of the dumps")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return _compile(args)
    out = args.out or tempfile.mkdtemp(prefix="kernel_bundles_")
    env = dict(os.environ, JAX_PLATFORMS="cpu", LIBTPU_INIT_ARGS=(
        "--xla_jf_dump_to=%s --xla_jf_dump_llo_text=true" % out))
    child = subprocess.run([sys.executable, os.path.abspath(__file__),
                            "--child"] + sys.argv[1:], env=env,
                           stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                           text=True)
    sys.stdout.write(child.stdout)
    listings = sorted(
        f for f in glob.glob(os.path.join(out, "*fluid_*final_bundles.txt"))
        if "schedule-analysis" not in f)
    if not listings:
        sys.exit("no kernel was dumped under %s (the child's exit code %d)"
                 % (out, child.returncode))
    for path in listings:
        with open(path) as f:
            bundles = f.readlines()
        print("%s: %d bundles, %d with a vmatmul, %d with a spill" % (
            os.path.basename(path).split("-", 1)[1], len(bundles),
            sum("vmatmul" in b for b in bundles),
            sum("_spill" in b for b in bundles)))
    print("listings under", out)


if __name__ == "__main__":
    main()
