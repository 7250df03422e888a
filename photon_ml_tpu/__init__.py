"""photon-ml-tpu: a TPU-native (JAX/XLA/pjit/pallas) framework for training
Generalized Linear Models and GAME/GLMix mixed-effect models at scale.

Brand-new design with the capabilities of LinkedIn Photon-ML (reference
surveyed in SURVEY.md). The compute path is pure JAX: jit-compiled
``lax.while_loop`` optimizers (LBFGS/OWLQN/TRON), segment-sum sparse GLM
objectives, ``psum`` data-parallel reductions over a device mesh, and
``vmap``-batched per-entity random-effect solvers.
"""

import time as _time

_t0 = _time.perf_counter()

from photon_ml_tpu import _import_clock  # noqa: E402

_outermost = _import_clock.enter()
try:
    __version__ = "0.1.0"

    from photon_ml_tpu.ops.losses import (  # noqa: F401
        LOSSES,
        LogisticLoss,
        PointwiseLoss,
        PoissonLoss,
        SmoothedHingeLoss,
        SquaredLoss,
        get_loss,
    )
    from photon_ml_tpu.ops.sparse import SparseBatch  # noqa: F401
    from photon_ml_tpu.ops.objective import GLMObjective  # noqa: F401
    from photon_ml_tpu.training import (  # noqa: F401
        SweepEntry,
        select_best_model,
        train_glm,
    )
finally:
    # the package's import, jax and pallas included: counter import.seconds
    _import_clock.leave(_outermost, _t0)
