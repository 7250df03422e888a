from photon_ml_tpu.utils.compile_cache import enable_compile_cache  # noqa: F401
from photon_ml_tpu.utils.timing import Timer, logger, setup_logging, timed  # noqa: F401
