"""The benchmark's harness: everything that decides a number lives here,
under BENCHMARK.json's ``paths``, where a PR that claims a gain cannot
change it.  From the program (``paddle_tpu``) the harness takes only the
system under test and its spans and counters."""
