from paddle_tpu.obs.timeline import setup_phase as _setup_phase

with _setup_phase("import"):   # the set-up record: this package's import
    from paddle_tpu.trainer.trainer import SGDTrainer
    from paddle_tpu.trainer import events
    from paddle_tpu.trainer.checkpoint import (
        save_checkpoint,
        load_checkpoint,
        save_pytree,
        load_pytree,
        latest_pass,
        latest_valid_pass,
        validate_checkpoint,
        read_manifest,
    )
    from paddle_tpu.trainer.checkgrad import check_gradients
