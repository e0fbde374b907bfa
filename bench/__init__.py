"""The chip benchmark: one cell (configuration x traffic mix) per run.

    python3 -m bench.run --workload <config>.<traffic> --seed N \
        --seconds S --trace 0|1

Everything that decides a number lives here, apart from the program under
test: traffic generation, the weights made from the seed, the plain float32
reference and the comparison that decides ``correct``, the table of peaks,
the FLOP and byte counts, and the reduction of the profiler trace.
"""
