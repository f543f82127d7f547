"""The benchmark's own library: traffic, weights, the open-loop drive, the
trace reduction, the yardstick (peaks and cost counts) and the references
that decide ``correct``. Nothing here is imported by the program."""
