from repro.optim.adamw import (AdamWConfig, adamw_update, init_opt_state,
                               abstract_opt_state, schedule_lr, global_norm,
                               clip_by_global_norm)

__all__ = ["AdamWConfig", "adamw_update", "init_opt_state",
           "abstract_opt_state", "schedule_lr", "global_norm",
           "clip_by_global_norm"]
