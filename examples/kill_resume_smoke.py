"""Kill-and-resume crash-safety smoke (DESIGN.md §9): SIGKILL a
checkpointed faulted sweep mid-run, resume it, and demand the resumed
trajectory is bit-identical to an uninterrupted one.

A child process runs ``run_policy_streams(..., checkpoint_dir=, chunk=)``
with the checkpoint writer wrapped to SIGKILL the process after N saves —
a hard crash at a chunk boundary, no atexit, no cleanup.  The parent then
resumes from the surviving checkpoints and compares every PolicyResult
field (queue_len/occupancy/departed plus the dropped/truncated and
preempted/requeued/lost counters) against the straight-through run.

Exits nonzero on any mismatch; CI runs this as the crash-safety gate.
"""
import os
import signal
import subprocess
import sys
import tempfile

import numpy as np

# Shared by parent and child so both build the SAME streams + config.
SETUP = """
import jax
from repro.core.engine.streams import make_streams

def build():
    def sampler(key, n):
        return jax.random.uniform(key, (n,), minval=0.1, maxval=0.6)
    return make_streams(jax.random.PRNGKey(5), 0.6, 0.5, sampler,
                        L=4, K=8, A_max=4, horizon=240,
                        fault_rate=0.02, repair_rate=0.3)

CFG = dict(policy="bfjs", engine="scan", L=4, K=8, Qcap=64, A_max=4)
CHUNK = 60
"""

# Child: run the chunked sweep, SIGKILL ourselves after `kill_after`
# checkpoint writes land on disk.  Reaching the end means the kill never
# fired — that is a failure of the harness, not a pass.
CHILD = SETUP + """
import os, signal, sys
import repro.core.engine.streaming as streaming
from repro.core.engine.api import run_policy_streams

kill_after, ckpt_dir = int(sys.argv[1]), sys.argv[2]
_real_save, _calls = streaming._save_step, 0

def _killing_save(*args, **kwargs):
    global _calls
    _real_save(*args, **kwargs)
    _calls += 1
    if _calls >= kill_after:
        os.kill(os.getpid(), signal.SIGKILL)

streaming._save_step = _killing_save
run_policy_streams(build(), checkpoint_dir=ckpt_dir, chunk=CHUNK, **CFG)
sys.exit("survived past the kill point — harness broken")
"""


def main() -> None:
    ns: dict = {}
    exec(SETUP, ns)
    streams, cfg, chunk = ns["build"](), ns["CFG"], ns["CHUNK"]

    from repro.core.engine.api import run_policy_streams

    full = run_policy_streams(streams, **cfg)
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep \
        + env.get("PYTHONPATH", "")

    for kill_after in (1, 2, 3):
        with tempfile.TemporaryDirectory() as ckpt_dir:
            proc = subprocess.run(
                [sys.executable, "-c", CHILD, str(kill_after), ckpt_dir],
                env=env)
            if proc.returncode != -signal.SIGKILL:
                raise SystemExit(
                    f"child exited {proc.returncode}, expected SIGKILL "
                    f"({-signal.SIGKILL})")
            res = run_policy_streams(streams, checkpoint_dir=ckpt_dir,
                                     chunk=chunk, resume=True, **cfg)
            for f in full._fields:
                a, b = np.asarray(getattr(res, f)), \
                    np.asarray(getattr(full, f))
                if a.shape != b.shape or not np.array_equal(a, b):
                    raise SystemExit(
                        f"resume after SIGKILL@save#{kill_after} diverged "
                        f"on {f!r}")
            print(f"SIGKILL after save #{kill_after}: resume bit-matches "
                  "the uninterrupted run")
    print("kill-and-resume smoke PASSED "
          f"(preempted={int(full.preempted)} requeued={int(full.requeued)} "
          f"lost={int(full.lost)})")


if __name__ == "__main__":
    main()
