"""Device time a step spends in the flash-attention custom calls (forward
and backward, every layer): their share of the traced steps times the
median step time."""

from chipbench.common import kernel_ms_per_step as read  # noqa: F401
