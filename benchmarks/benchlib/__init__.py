"""The benchmark's yardstick: harness, pass function, chain cache,
compile meter and trace reduction.  Nothing here is specific to one
configuration, traffic mix or per-layer metric — those are files found
by name (../README.md)."""
