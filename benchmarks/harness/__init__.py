"""The benchmark's yardstick: loader, clocks, trace reduction, peaks,
work counts, the plain reference and the comparison.  Later PRs add
files beside these and may not edit them."""
