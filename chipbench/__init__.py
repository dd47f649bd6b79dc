"""chipbench: the benchmark of TNN-TPU on a TPU v5e (see chipbench/README.md).

Everything that decides a number lives here: traffic generation, the
reduction from traces and counters to metrics, the table of peaks, the
operation and byte counts, the plain references and the comparison that
decides ``correct``. From the program it takes only the system under test.
"""
