"""CPU tests of the chip benchmark's harness, yardstick and checks."""
