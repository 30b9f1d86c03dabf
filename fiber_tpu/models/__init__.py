"""Model zoo for the framework's population-based workloads: policy
networks and pure-JAX environments whose rollouts compile end-to-end."""

from fiber_tpu.models.policies import (  # noqa: F401
    ConvPolicy,
    GRUPolicy,
    MLPPolicy,
)
from fiber_tpu.models.transformer import (  # noqa: F401
    Block,
    BlockLM,
    ExitGate,
    Experts,
    Latent,
    MTP,
    Rope,
    StateSpace,
    TinyLM,
    Yarn,
    make_train_step,
)
from fiber_tpu.models.envs import (  # noqa: F401
    CartPole,
    DeceptiveMaze,
    ParamBipedWalker,
    ParamCartPole,
    ParamHillWalker,
    Pendulum,
    PixelChase,
    rollout_recurrent,
)
