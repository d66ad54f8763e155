"""The benchmark of the simulator on the chip (see run.py and BENCHMARK.json)."""
