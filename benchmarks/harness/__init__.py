"""The benchmark's harness: traffic, client, statistics, the trace
reduction, the roofline arithmetic and the output check. It takes from
the program only the system under test and its counters."""
