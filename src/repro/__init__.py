"""Phocas reproduction package."""
