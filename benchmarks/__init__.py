"""Paper-figure benchmarks (Figs. 9-14, Tables IV-V) and the S/C sweeps."""
