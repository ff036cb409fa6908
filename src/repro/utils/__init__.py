from .sysinfo import cpu_time_s, peak_rss_mb, rss_mb

__all__ = ["cpu_time_s", "peak_rss_mb", "rss_mb"]
