"""chipbench: the repository's benchmark on the chip (see README.md here).

The yardstick lives in this directory: traffic generation, the reduction
from traces and counters to metrics, the table of peaks, the closed-form
FLOPs, each configuration's plain reference and the comparison that
decides ``correct``.  From the program it takes only the system under
test and its counters.
"""
