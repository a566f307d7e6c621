"""chipbench's harness: loader, drivers, clocks, trace reduction, last line."""
