"""Training runtime: state, jitted steps, checkpointing, epoch loops,
fault tolerance."""

from .checkpoint import (CheckpointCorrupt, CheckpointSaver,
                         ShardedCheckpointSaver, find_resume_candidates,
                         load_checkpoint_file, replicate_for_save,
                         restore_any, restore_resharded,
                         restore_sharded_checkpoint, restore_train_state,
                         restore_with_fallback, resume_position,
                         save_checkpoint_file, save_sharded_checkpoint,
                         wait_pending_saves)
from .resilience import (EXIT_PREEMPTED, EXIT_WATCHDOG, AnomalyGuard,
                         Preempted, Resilience, RewindRequested,
                         StallWatchdog, allreduce_flags)
from .state import (TrainState, create_train_state, get_learning_rate,
                    set_learning_rate)
from .steps import make_eval_step, make_train_step
from .trainer import save_image_batch, train_one_epoch, validate
