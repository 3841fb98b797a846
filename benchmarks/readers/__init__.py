"""Per-layer (and end-to-end) metric readers. A metric's file under
``metrics/`` names one of these modules and its arguments; ``read(obs,
**args)`` takes the metric from what the run observed (request records,
engine counters, step times, the reduced trace) and returns None where
there is nothing to read."""
