"""repro.dist — the distribution subsystem.

* ``logical``     logical-axis sharding rules + the ``lc`` constraint helper
* ``elastic``     degraded-device mesh selection
* ``compression`` gradient codecs (bf16 / stochastic int8) + error feedback
"""
from repro.dist import compression, elastic, logical

__all__ = ["compression", "elastic", "logical"]
