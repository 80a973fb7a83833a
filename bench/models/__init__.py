"""One module per architecture: the benchmark's weights, the plain reference
layer, the forward FLOPs, and how to build the program's model."""
