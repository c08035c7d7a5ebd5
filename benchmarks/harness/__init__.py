"""The benchmark's harness: the yardstick that later changes measure
against and may not edit."""
