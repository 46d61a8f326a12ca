"""Survey reproduction package."""
