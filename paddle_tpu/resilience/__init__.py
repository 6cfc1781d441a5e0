"""``paddle_tpu.resilience`` — fault-tolerant training subsystem.

Production TPU training is preemption-dominated; this package makes every
tier of the trainer survivable (docs/resilience.md):

- **checkpoint_io** — atomic, CRC-verified ``pass-%05d`` checkpoints with
  a manifest (per-array CRC32 + original dtypes + wall-clock + meta),
  ``keep_last_n`` retention, and a validating ``latest_pass`` that skips
  corrupt directories;
- **guard** — in-jit finite checks on loss and gradient global-norm; a
  bad step is held by a select in each leaf's own optimizer update (no
  ``lax.cond`` over the state, no host syncs; audited by
  ``paddle_tpu.analysis``);
- **reader** — ``resilient_reader`` retry/backoff/skip-bad-batch wrapper;
- **signals** — SIGTERM/SIGINT -> checkpoint-at-batch-boundary + clean
  exit (``PreemptionHandler``), gang-agreed when a cluster context is
  attached;
- **cluster** — the gang-supervised runtime (docs/resilience.md
  "Multi-host recovery"): ``GangSupervisor`` kills and relaunches the
  whole gang on rank death or heartbeat stall (bounded restarts,
  exponential backoff, per-rank attribution in ``GangFailedError``);
  ``current_gang()`` gives workers the barrier / preemption-OR /
  coordinator-broadcast primitives that make checkpoints and resume
  multi-host-consistent;
- **chaos** — fault injection (corrupt/truncate checkpoints, NaN-grad
  batches, flaky readers, simulated preemptions, rank kill/hang) proving
  each recovery path end-to-end in tests/test_resilience.py and
  tests/test_gang.py.
"""

from paddle_tpu.resilience.errors import (CheckpointError, DCNError,
                                          DCNPartitioned, DCNTimeout,
                                          GangError, GangFailedError,
                                          GangResized, ReaderError,
                                          SDCDivergence, TooManyBadSteps)
from paddle_tpu.resilience.cluster import (GangContext, GangResult,
                                           GangSupervisor, RankReport,
                                           current_gang)
from paddle_tpu.resilience.checkpoint_io import (MANIFEST_VERSION,
                                                 latest_pass,
                                                 latest_valid_pass,
                                                 load_checkpoint,
                                                 load_pytree, npz_safe,
                                                 pass_dir,
                                                 prune_checkpoints,
                                                 read_manifest,
                                                 save_checkpoint,
                                                 save_pytree,
                                                 validate_checkpoint)
from paddle_tpu.resilience.guard import (global_grad_norm, guarded_update,
                                         init_loss_scale,
                                         scaled_guarded_update)
from paddle_tpu.resilience.integrity import (ScrubDaemon, fingerprint_hex,
                                             fingerprint_int,
                                             latest_verified_pass,
                                             make_agreement_check,
                                             np_tree_fingerprint,
                                             scrub_paths, sdc_vote,
                                             sdc_vote_pods,
                                             tree_fingerprint)
from paddle_tpu.resilience.reader import resilient_reader
from paddle_tpu.resilience.signals import PreemptionHandler
from paddle_tpu.resilience import chaos

__all__ = [
    "CheckpointError",
    "ReaderError",
    "TooManyBadSteps",
    "GangError",
    "GangFailedError",
    "GangResized",
    "DCNError",
    "DCNTimeout",
    "DCNPartitioned",
    "GangContext",
    "GangResult",
    "GangSupervisor",
    "RankReport",
    "current_gang",
    "MANIFEST_VERSION",
    "npz_safe",
    "save_pytree",
    "load_pytree",
    "save_checkpoint",
    "load_checkpoint",
    "read_manifest",
    "validate_checkpoint",
    "latest_pass",
    "latest_valid_pass",
    "prune_checkpoints",
    "pass_dir",
    "global_grad_norm",
    "guarded_update",
    "init_loss_scale",
    "scaled_guarded_update",
    "resilient_reader",
    "PreemptionHandler",
    "chaos",
    "SDCDivergence",
    "tree_fingerprint",
    "np_tree_fingerprint",
    "fingerprint_int",
    "fingerprint_hex",
    "sdc_vote",
    "sdc_vote_pods",
    "make_agreement_check",
    "scrub_paths",
    "latest_verified_pass",
    "ScrubDaemon",
]
