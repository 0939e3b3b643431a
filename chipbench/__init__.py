"""chipbench: the on-chip benchmark of this repository. See README.md."""
