"""NBL reproduction package."""
