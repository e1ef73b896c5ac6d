"""The on-chip benchmark's yardstick: cells, weights, traffic, reference,
trace reduction and work counts. Nothing here imports the program except
to drive it (`drivers/`) and to build its configuration object."""
