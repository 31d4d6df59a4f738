"""Mean processor time of the scheduler's thread a cycle (`thread_cpu_us`
over `cpu_cycles` of the program's `slotpool.cycle` markers: the program
reads `time.thread_time` four times a second and at every slow cycle,
for the cycles since its last reading): the part of a cycle's host time that is
Python on the processor, as against waiting for the runtime, the lock
or the device. Layer: serving scheduler; moves serve_tokens_per_s."""
from benchmark.chip import cycle_spans


def read(obs):
    return cycle_spans.thread_cpu_ms(obs)
