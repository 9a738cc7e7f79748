from paddle_tpu.utils.compile_cache import enable_compile_cache  # noqa: F401
from paddle_tpu.utils.flags import FLAGS, define_flag, parse_flags  # noqa: F401
from paddle_tpu.utils.logger import get_logger  # noqa: F401
from paddle_tpu.utils.stat import StatSet, global_stat, timer  # noqa: F401
