"""The benchmark's own code: traffic generation, the load generator's
child process, the reduction from traces and counters to metrics, the
plain reference and the comparison that decides `correct`. Nothing here
is imported by the program under test."""
