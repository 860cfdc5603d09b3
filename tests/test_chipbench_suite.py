"""The benchmark's own tests (``chipbench/tests``), collected here as they
are so that tier-1 runs them: they hold the program to every name the
benchmark reaches for (``_fused_cache``, ``compiled_on_last_call``,
``_place_ops``, ``decide_apply``, the span names). CPU only.
"""

from chipbench.tests.test_chipbench import *  # noqa: F401,F403
from chipbench.tests.test_cycle_spans import *  # noqa: F401,F403
