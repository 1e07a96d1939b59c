"""The benchmark's yardstick: traffic, jobs, references, reductions.

Nothing here is imported by the program; later PRs may add files beside
these and may not edit them (see ../README.md)."""
