"""The benchmark's tests run on the CPU with host devices (four are
enough for the four-chip cell's mesh); they never load the TPU's
library."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
