"""Seeded traffic generators; a traffic file names one."""
