"""Plain float32 references of the benchmark's networks.

Each module builds its network from the seed alone and steps it in
straightforward ``jax.numpy``; none imports the simulator under test.
"""
