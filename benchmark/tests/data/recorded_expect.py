"""What small_v5e.xplane.pb holds, read independently of
trace_reduce from the Perfetto JSON the profiler wrote beside it in
the same chip run (PR 22): four jitted 1024x1024 matmuls, each after
a 20 ms host sleep under bench.initialize, inside bench.window.  The
two files round timestamps differently, so the comparison allows a
few nanoseconds."""

WINDOW_S = 0.086075492
BUSY_S = 4.743125e-05
OPS_S = {"%fusion": 4.7362344e-05}
LONGEST_GAP = ("bench.initialize", 0.021805061)
