"""SpiNNaker 2 processing-element architecture as a JAX/Pallas system."""
