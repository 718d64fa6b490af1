"""End-to-end driver: train a small LM (the reduced xlstm-350m config, about
0.6 M parameters, sized for a CPU) for a few hundred steps under the
unified FT framework (checkpoint + replication), with injected failures,
and verify the FT theorem: final parameters match a failure-free run
exactly.  ``chip_smoke.py`` makes the same check at full width on a TPU.

This is the training analogue of the paper's HPCG experiments, driven
through the unified ``repro.ft`` API (FTSession + TrainWorkload): the
replica slice redundantly executes every step; a computational-slice kill
promotes the replica (no rollback); a pair-death falls back to the last
Young-Daly checkpoint.

  PYTHONPATH=src python examples/train_lm_ft.py [--steps 200]
"""
import argparse
import sys
import tempfile

sys.path.insert(0, "src")

import numpy as np

from repro.configs.base import FTConfig
from repro.launch.train import build_session

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=200)
ap.add_argument("--arch", default="xlstm-350m")
args = ap.parse_args()

kills = {args.steps // 4: [0],                  # cmp slice dies -> promote
         args.steps // 2: [1, 9],               # cmp + its replica -> restart
         3 * args.steps // 4: [10]}             # replica dies -> drop

with tempfile.TemporaryDirectory() as d:
    ft = FTConfig(mode="combined", mtbf_s=1e9, ckpt_interval_s=25.0)
    session, workload = build_session(
        args.arch, reduced=True, batch=8, seq=128, ft=ft, ckpt_dir=d,
        kill_schedule=dict(kills), n_logical_workers=8)
    rep_f = session.run(workload, args.steps)

clean_session, clean_workload = build_session(
    args.arch, reduced=True, batch=8, seq=128, ft=FTConfig(mode="none"))
rep_c = clean_session.run(clean_workload, args.steps)

print(f"faulty : steps={rep_f.steps} failures={rep_f.failures} "
      f"promotions={rep_f.promotions} restarts={rep_f.restarts} "
      f"ckpts={rep_f.ckpt_writes} loss={rep_f.losses[-1]:.5f}")
print(f"clean  : steps={rep_c.steps} loss={rep_c.losses[-1]:.5f}")
print("event stream:", [(e.step, e.kind) for e in rep_f.events])

import jax
fa = jax.tree.leaves(rep_f.final_state["params"])
cl = jax.tree.leaves(rep_c.final_state["params"])
worst = max(float(np.max(np.abs(np.asarray(a, np.float32) -
                                np.asarray(b, np.float32))))
            for a, b in zip(fa, cl))
print(f"max |param diff| faulty vs clean: {worst:.3e}")
assert worst == 0.0, "FT theorem violated: failures changed the result"
print("FT THEOREM HOLDS: failures + promotion + restart left training "
      "bitwise identical.")
