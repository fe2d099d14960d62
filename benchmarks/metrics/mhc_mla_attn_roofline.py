"""``mla_attn_roofline`` on the ``xing4_0`` configuration's cell: the
same reader (the cached row is the family's, 1,152 bytes a layer and
token whatever the streams; the step programs hold no other Pallas
call, the stream mixes being plain XLA). Source: device trace
(operation line)."""

from metrics.mla_attn_roofline import read  # noqa: F401
