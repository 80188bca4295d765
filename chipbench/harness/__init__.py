"""chipbench: the benchmark's own yardstick. Traffic generation, metric
arithmetic, the plain reference, the trace reduction and the table of peaks
live here, where a PR that claims a gain cannot change them. From the program
it takes only the engine under test and its counters."""
