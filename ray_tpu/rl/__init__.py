"""``ray_tpu.rl`` — Podracer-style actor/learner RL for the GPT family.

The train<->infer loop, closed (ROADMAP item 3): **actor replicas**
wrap the continuous-batching inference engine to generate rollout
trajectories (sampled completions + the sampler's own chosen-token
logprobs), **learner replicas** run the REINFORCE/RLOO policy-gradient
step derived from ``models/training.py`` (:func:`~ray_tpu.models.
training.build_gpt_rl_train`), and the two meet through the object
store: the learner publishes versioned weight snapshots
(:class:`~ray_tpu.rl.replay.WeightStore`) that actors hot-swap with a
donated-buffer, zero-recompile ``engine.set_params``, while trajectory
batches flow back through a bounded, staleness-bounded
:class:`~ray_tpu.rl.replay.ReplayQueue`.  The Sebulba split of
arXiv:2104.06272, with arXiv:2011.03641's concurrency-limits argument
applied to staleness: separate replica pools, hard version-lag bound.

Config via ``RAY_TPU_RL_*`` (:func:`rl_config`); ``run_rl_loop`` is
the driver; the
RLlib :class:`~ray_tpu.rllib.core.learner_group.LearnerGroup` hosts
multi-learner DDP via ``learner_cls="ray_tpu.rl.learner.
GPTPolicyLearner"``.
"""

from ray_tpu.rl.config import RLConfig, rl_config  # noqa: F401
from ray_tpu.rl.learner import (GPTPolicyLearner,  # noqa: F401
                                InProcessLearner, LearnerGroupAdapter,
                                RLLearnerConfig)
from ray_tpu.rl.loop import run_rl_loop  # noqa: F401
from ray_tpu.rl.replay import (ReplayPutTimeout,  # noqa: F401
                               ReplayQueue, WeightStore)
from ray_tpu.rl.reward import (batch_rewards,  # noqa: F401
                               target_token_reward)
from ray_tpu.rl.rollout import (RolloutActor,  # noqa: F401
                                TrajectoryBatch, trajectories_to_batch)

__all__ = [
    "RLConfig", "rl_config",
    "RolloutActor", "TrajectoryBatch", "trajectories_to_batch",
    "ReplayQueue", "ReplayPutTimeout", "WeightStore",
    "InProcessLearner", "GPTPolicyLearner", "LearnerGroupAdapter",
    "RLLearnerConfig",
    "target_token_reward", "batch_rewards",
    "run_rl_loop",
]
