"""The benchmark's own library: lookup by name, device checks, spans,
trace reduction, the traffic generator, work counts and the plain
reference. Nothing here is imported by the program under test."""
