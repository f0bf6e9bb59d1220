from .queries import grid_distance_queries

__all__ = ["grid_distance_queries"]
