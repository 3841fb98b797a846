"""The benchmark (BENCHMARK.json at the checkout's root runs it): cells
are data files found by name, the yardstick is code kept here. Start at
``run.py``; ``PERF.md`` says what each metric means."""
