"""``moe_experts_touched_pct`` on the ``xing4_0`` configuration's cell:
the same reader (layers and experts come from the configuration's
keys). Source: the program's pass records (``experts_touched``)."""

from metrics.moe_experts_touched_pct import read  # noqa: F401
