"""Share of the traced steps in which no operation ran on the device."""

from chipbench.common import idle_share as read  # noqa: F401
