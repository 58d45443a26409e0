"""Errors every layer may raise."""


class PlanError(ValueError):
    """A statement the system refuses: an unknown name, a bad option
    value, a query shape the planner does not take. The session turns
    it into the client's error message."""
