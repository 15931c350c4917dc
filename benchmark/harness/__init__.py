"""What every driver shares: processes, the job, log parsing, arithmetic."""
