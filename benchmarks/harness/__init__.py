"""The benchmark's yardstick: cell loading, arithmetic, traffic
generation, the plain references, trace reduction, peaks and byte
counts. Nothing here imports the program except to build the input
objects (vertices) its entry points take."""
